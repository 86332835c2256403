"""``correct`` on the Lanczos cells at small sizes: sound runs pass, and
the control (the reference one precision step down, and the program's own
bfloat16 value path) and each planted fault fail the cell's limit."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common, control, run  # noqa: E402

SEED = 2**31 + 4099


def small(name: str) -> common.Cell:
    """The cell as committed, with its operator cut to a test's size."""
    cell = common.resolve(name, ROOT)
    cfg = dict(cell.config)
    cfg.pop("sha256", None)
    cfg["n"] = 12_000
    cell.config = cfg
    return cell


def run_small(name, kind="sound", value_dtype=None, seconds=0.4):
    import jax
    cell = small(name)
    op = common.operator_module(cell).build(cell.config, SEED)
    env = {"compile_plan": control.plan_with(kind, op.host, value_dtype)}
    return run.run_cell(cell, SEED, seconds, False, jax.devices(), env=env)


@pytest.mark.parametrize("name", ["hh_lanczos"])
def test_sound_runs_are_correct(name):
    res = run_small(name)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert res["checks"]["ritz_gap"]["value"] < res["checks"]["ritz_gap"]["limit"] / 3


@pytest.mark.parametrize("name", ["hh_lanczos"])
def test_bf16_reference_in_the_programs_place_fails(name):
    res = run_small(name, "bf16_reference")
    assert res["correct"] is False, res["checks"]


def test_the_programs_bf16_value_path_fails():
    res = run_small("hh_lanczos", value_dtype="bf16")
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("name", ["hh_lanczos"])
@pytest.mark.parametrize("kind", ["state_unchanged", "answer_altered"])
def test_planted_faults_fail(name, kind):
    res = run_small(name, kind)
    assert res["correct"] is False, res["checks"]


def test_the_traced_window_calls_the_plan_once_before_its_solves():
    import jax

    from bench.placement import compile_plan
    cell = small("hh_lanczos")
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
    assert win["solves"] >= 1 and win["spmv_calls"] == 60 * win["solves"]
    assert len(calls) - before == win["spmv_calls"] + 1   # the probe, outside the solves
