"""The ``graph500_pagerank`` cell at a small scale: ``correct`` passes sound
runs and fails the control and the planted faults; the cell reports its
metrics; compulsory bytes and the per-iteration idle reader."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common, compulsory, control, run  # noqa: E402
from bench.trace import Device, Summary  # noqa: E402

CELL = "graph500_pagerank"
SEED = 2**31 + 8191
MS = 1e6   # ns


def small(scale: int = 12) -> common.Cell:
    """The cell as committed, with its graph cut to a test's scale."""
    cell = common.resolve(CELL, ROOT)
    cfg = dict(cell.config, scale=scale, n=1 << scale)
    cfg.pop("sha256", None)
    cell.config = cfg
    return cell


def run_small(kind="sound", value_dtype=None, seconds=0.4):
    import jax
    cell = small()
    op = common.operator_module(cell).build(cell.config, SEED)
    env = {"compile_plan": control.plan_with(kind, op.host, value_dtype)}
    return run.run_cell(cell, SEED, seconds, False, jax.devices(), env=env)


def test_sound_runs_are_correct():
    res = run_small()
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    err = res["checks"]["score_l1_err"]
    assert err["value"] < err["limit"] / 3
    assert res["checks"]["iterations_gap"]["value"] == 0
    assert {"solve_s", "setup_s"} <= set(res["metrics"])


@pytest.mark.parametrize("kind,value_dtype", [("bf16_reference", None), ("sound", "bf16"),
                                              ("state_unchanged", None),
                                              ("answer_altered", None)])
def test_the_control_and_the_planted_faults_fail(kind, value_dtype):
    res = run_small(kind, value_dtype)
    assert res["correct"] is False, res["checks"]


def test_the_cell_resolves_and_reports_solve_setup_and_layers():
    cell = common.resolve(CELL, ROOT)
    assert cell.config["placement"] == {"kind": "single", "chips": 1, "format": "auto"}
    assert cell.config["symmetric"] is False and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"solve_s", "setup_s"}
    layers = {m["name"] for m in cell.per_layer}
    assert {"spmv_roofline.solve", "step_idle_ms.pagerank", "launches_per_step.solve",
            "device_idle.solve", "build_s", "plan_s"} <= layers
    assert "step_idle_ms.solve" not in layers


def test_compulsory_bytes_count_every_stored_value():
    cell = small(10)
    op = common.operator_module(cell).build(cell.config, SEED)
    assert op.n_diag is None and op.stored_values and op.n == 1024
    assert op.nnz == op.host.a64.nnz
    assert compulsory.spmv_bytes(op.n, op.n, op.nnz, op.dtype, op.stored_values,
                                 op.n_diag) == 4 * op.nnz + 8 * op.n


def test_the_traced_window_calls_the_plan_once_before_its_solves():
    import jax

    from bench.placement import compile_plan
    cell = small(10)
    op = common.operator_module(cell).build(cell.config, SEED)
    ctx = common.Context(cell, SEED, 0.2, True)
    calls = []

    def counted_plan(config, matrix, devices):
        plan, info = compile_plan(config, matrix, devices)

        def apply(x):
            calls.append(x.shape)
            return plan(x)
        return apply, info
    drv = common.runner(cell).Runner(ctx, op, {"devices": jax.devices(),
                                               "compile_plan": counted_plan})
    before = len(calls)
    win = drv.window(0.2, annotate=True)
    assert win["solves"] >= 1 and win["spmv_calls"] == win["steps"] >= win["solves"]
    assert len(calls) - before == win["spmv_calls"] + 1   # the probe, outside the solves


def test_the_check_samples_the_first_solve_and_draws_from_the_seed():
    import jax
    cell = small(10)
    op = common.operator_module(cell).build(cell.config, SEED)
    env = {"devices": jax.devices(),
           "compile_plan": control.plan_with("sound", op.host)}
    drv = common.runner(cell).Runner(common.Context(cell, SEED, 0.3, False), op, env)
    win = drv.window(0.3)
    k = cell.traffic["check_solves"]
    assert win["solves"] > k
    first = win["records"][0][0]
    picked = []
    teleport = drv.teleport
    drv.teleport = lambda i: picked.append(i) or teleport(i)
    checks = drv.check(win)
    again = list(picked)
    picked.clear()
    assert drv.check(win) == checks and picked == again   # the sample is the seed's
    assert len(picked) == k and picked[0] == first and len(set(picked)) == k
    assert checks["score_l1_err"] < cell.limits["score_l1_err"]


def test_the_check_sees_how_the_program_normalised_P(monkeypatch):
    """The reference builds P from the pattern alone, so a program that
    scales by the row's degree in place of the column's fails."""
    import jax

    from repro.core import matrices
    from repro.core.formats import CSR

    def by_row_degree(adj, dtype=np.float32):
        rp = np.asarray(adj.row_ptr)
        deg = np.diff(rp).astype(np.float64)
        val = (1.0 / np.repeat(deg, np.diff(rp))).astype(dtype)
        return CSR(rp.copy(), np.asarray(adj.col_idx).copy(), val, tuple(adj.shape))
    monkeypatch.setattr(matrices, "pagerank_transition", by_row_degree)
    cell = small()
    op = common.operator_module(cell).build(cell.config, SEED)
    ref = np.asarray(op.host.a64.sum(axis=0)).ravel()
    assert np.allclose(ref[ref > 0], 1.0)             # the reference's columns sum to 1
    env = {"compile_plan": control.plan_with("sound", op.host)}
    res = run.run_cell(cell, SEED, 0.4, False, jax.devices(), env=env)
    assert res["correct"] is False, res["checks"]


# --- step_idle_ms.pagerank ----------------------------------------------------

def _summary():
    """One traced solve (0-30 ms) holding one PageRank solve (1-21 ms) of
    two iterations; programs run 2-5, 7-10, 12-15 and 16-19 ms, and 24-26
    ms after it.  Idle inside the solve: 20 - 12 = 8 ms."""
    progs = [("jit_spmv_sell_xla", 2 * MS, 5 * MS), ("jit__pagerank_update", 7 * MS, 10 * MS),
             ("jit_spmv_sell_xla", 12 * MS, 15 * MS),
             ("jit__pagerank_update", 16 * MS, 19 * MS),
             ("jit_bench_teleport", 24 * MS, 26 * MS)]
    host = [("solve", 0.0, 30 * MS, 0, True),
            ("pagerank", 1 * MS, 21 * MS, 1, True),
            ("pagerank.step", 1 * MS, 11 * MS, 2, True),
            ("pagerank.sync", 5 * MS, 11 * MS, 3, True),
            ("pagerank.step", 11 * MS, 21 * MS, 2, True),
            ("pagerank.sync", 15 * MS, 21 * MS, 3, True)]
    return Summary([Device("/device:TPU:0", progs, 0.0)], host)


def _ctx(summary, steps=2):
    return SimpleNamespace(trace_summary=summary, trace_window=(0.0, 30 * MS),
                           window={"steps": steps})


def test_step_idle_pagerank_is_the_idle_inside_the_solve_per_iteration():
    read = common.load_module(ROOT / "bench" / "metrics" / "step_idle_ms.pagerank.py").read
    assert read(_ctx(_summary())) == pytest.approx(4.0)


def test_step_idle_pagerank_is_none_unless_the_step_spans_count_the_iterations():
    read = common.load_module(ROOT / "bench" / "metrics" / "step_idle_ms.pagerank.py").read
    assert read(_ctx(_summary(), steps=3)) is None
    assert read(_ctx(_summary(), steps=0)) is None
    bare = _summary()
    bare.host = [h for h in bare.host if not h[0].startswith("pagerank")]
    assert read(_ctx(bare)) is None                   # a program without spans
    assert read(_ctx(None)) is None


def test_the_reference_stops_on_the_l1_change():
    from bench import pagerank_reference as ref
    P = np.array([[0.0, 1.0], [1.0, 0.0]])
    t = np.array([0.75, 0.25])
    x, iters, changes = ref.pagerank(lambda v: P @ v, t, 0.5, 1e-3, 50)
    assert iters == len(changes) < 50 and changes[-1] < 1e-3 <= changes[-2]
    # the fixed point of x = (1 - d) t + d P x
    np.testing.assert_allclose(x, np.linalg.solve(np.eye(2) - 0.5 * P, 0.5 * t), atol=1e-3)
    _, iters, _ = ref.pagerank(lambda v: P @ v, t, 0.5, 0.0, 7)
    assert iters == 7
