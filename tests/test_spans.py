"""Program spans and counters (``utils.spans``): totals, nesting and
exceptions; the Lanczos spans in a profiler trace; the named hybrid
executor and its part scopes; the plan spans and the counters they fold."""
import glob
import os
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as P
from repro.core.eigensolver import LanczosBreakdown, lanczos
from repro.core.matrices import holstein_hubbard_surrogate
from repro.core.plan import SpMVPlan
from repro.core.planconfig import PlanConfig
from repro.utils import spans

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _span(name):
    return spans.snapshot()["spans"].get(name, [0, 0.0])


def _plan_totals():
    got = spans.snapshot()["spans"]
    return {k: list(got.get(k, [0, 0.0])) for k in ("plan.select", "plan.convert", "plan.build")}


@pytest.fixture(scope="module")
def hh():
    return holstein_hubbard_surrogate(3_000, seed=0, dtype=np.float32)


# --- the facility -------------------------------------------------------------

def test_span_adds_one_count_and_its_seconds():
    c0, s0 = _span("test.spans.totals")
    for _ in range(3):
        t = time.perf_counter()
        with spans.span("test.spans.totals"):
            time.sleep(0.01)
        wall = time.perf_counter() - t
    c1, s1 = _span("test.spans.totals")
    assert c1 - c0 == 3
    assert 0.03 <= s1 - s0 <= 3 * wall + 0.05


def test_nested_spans_each_count_and_the_outer_holds_the_inner():
    (co, so), (ci, si) = _span("test.spans.outer"), _span("test.spans.inner")
    with spans.span("test.spans.outer"):
        for _ in range(2):
            with spans.span("test.spans.inner"):
                time.sleep(0.005)
    (co1, so1), (ci1, si1) = _span("test.spans.outer"), _span("test.spans.inner")
    assert (co1 - co, ci1 - ci) == (1, 2)
    assert so1 - so >= si1 - si >= 0.01


def test_a_span_is_recorded_when_its_body_raises():
    c0, _ = _span("test.spans.raises")
    with pytest.raises(KeyError):
        with spans.span("test.spans.raises"):
            raise KeyError("x")
    assert _span("test.spans.raises")[0] == c0 + 1


def test_counters_and_snapshot_is_a_copy():
    spans.count("test.spans.declared", 0)
    n0 = spans.snapshot()["counters"].get("test.spans.counter", 0)
    spans.count("test.spans.counter", 3)
    spans.count("test.spans.counter")
    snap = spans.snapshot()
    assert snap["counters"]["test.spans.counter"] == n0 + 4
    assert snap["counters"]["test.spans.declared"] == 0
    snap["counters"]["test.spans.counter"] = -1
    snap["spans"].clear()
    assert spans.snapshot()["counters"]["test.spans.counter"] == n0 + 4


def test_precompute_and_pack_stats_are_views_of_the_counters():
    from repro.core.distributed_plan import pack_stats
    from repro.kernels.cache import precompute_stats
    assert set(pack_stats()) == {"shard_packs", "format_selections"}
    stats = precompute_stats()
    assert "csr_row_ids" in stats and all(isinstance(v, int) for v in stats.values())
    counters = spans.snapshot()["counters"]
    assert stats == {k[len("precompute."):]: v for k, v in counters.items()
                     if k.startswith("precompute.")}


# --- solver spans -------------------------------------------------------------

def test_lanczos_emits_one_span_per_attempt_step_and_sync(hh):
    before = {k: _span(k)[0] for k in ("lanczos", "lanczos.step", "lanczos.sync")}
    p = SpMVPlan.compile(hh, PlanConfig(format="hybrid", backend="xla"))
    lanczos(p, hh.shape[0], m=6, reorthogonalize=False, dtype=jnp.float32)
    after = {k: _span(k)[0] for k in before}
    assert {k: after[k] - before[k] for k in before} == {
        "lanczos": 1, "lanczos.step": 6, "lanczos.sync": 6}


def test_a_restarted_lanczos_counts_each_attempt():
    n, calls = 64, [0]
    d = jnp.arange(1.0, n + 1.0, dtype=jnp.float32)

    def apply_once_broken(x):
        calls[0] += 1
        return x * jnp.nan if calls[0] == 1 else d * x

    before = _span("lanczos")[0], _span("lanczos.step")[0]
    r = lanczos(apply_once_broken, n, m=4, reorthogonalize=False, dtype=jnp.float32,
                on_breakdown="restart")
    assert r.n_iterations == 4
    assert (_span("lanczos")[0] - before[0], _span("lanczos.step")[0] - before[1]) == (2, 5)
    with pytest.raises(LanczosBreakdown):
        lanczos(lambda x: x * jnp.nan, n, m=4, dtype=jnp.float32)


def test_a_traced_lanczos_holds_its_step_spans_inside_one_solve_span(hh, tmp_path):
    from jax.profiler import ProfileData

    from bench import trace
    m = 5
    p = SpMVPlan.compile(hh, PlanConfig(format="hybrid", backend="xla"))
    lanczos(p, hh.shape[0], m=m, reorthogonalize=False, dtype=jnp.float32)  # compile
    tmp = str(tmp_path)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test.main_thread"):
            lanczos(p, hh.shape[0], m=m, reorthogonalize=False, dtype=jnp.float32)
    finally:
        jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True),
               key=os.path.getsize)

    # every program span lies on the line of the main thread's own annotation
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                lines.setdefault(e.name, set()).add((plane.name, line.name))
    main = lines["test.main_thread"]
    assert len(main) == 1
    for name in ("lanczos", "lanczos.step", "lanczos.sync"):
        assert lines[name] == main, name

    s = trace.load(path)
    (_, a, b, depth, _), = [h for h in s.host if h[0] == "lanczos"]
    steps = [h for h in s.host if h[0] == "lanczos.step"]
    syncs = [h for h in s.host if h[0] == "lanczos.sync"]
    assert len(steps) == len(syncs) == m
    assert all(a <= s0 and e0 <= b and d == depth + 1 for _, s0, e0, d, _ in steps)
    assert all(any(s0 <= s1 and e1 <= e0 for _, s0, e0, *_ in steps)
               for _, s1, e1, *_ in syncs)


# --- executors ----------------------------------------------------------------

def test_the_hybrid_executors_are_named_and_their_parts_scoped(hh):
    p = SpMVPlan.compile(hh, PlanConfig(format="hybrid", backend="xla"))
    x = jnp.ones(hh.shape[0], jnp.float32)
    low = p.kernel.lower(p.operands, x)
    assert re.match(r"module @jit_spmv_hybrid_xla\b", low.as_text())
    hlo = low.compile().as_text()
    assert hlo.startswith("HloModule jit_spmv_hybrid_xla")
    scopes = set(re.findall(r'op_name="jit\(spmv_hybrid_xla\)/(\w+)/', hlo))
    assert {"dia", "sell"} <= scopes
    X = jnp.ones((hh.shape[0], 2), jnp.float32)
    assert "@jit_spmm_hybrid_xla" in p.kernel_multi.lower(p.operands_multi, X).as_text()
    np.testing.assert_allclose(np.asarray(p(x)), np.asarray(p.apply(x)))


def test_dia_shifted_slices_counts_each_dia_xla_build():
    def count():
        return spans.snapshot()["counters"].get("dia.shifted_slices", 0)
    # a matrix of its own: the count is once per converted container
    hh = holstein_hubbard_surrogate(1_500, seed=5, dtype=np.float32)
    n0 = count()
    p = SpMVPlan.compile(hh, PlanConfig(format="hybrid", backend="xla"))
    assert len(p.matrix.dia.offsets) and count() == n0 + 1  # SpMV and SpMM share it
    SpMVPlan.compile(hh, PlanConfig(format="sell", backend="xla"))
    assert count() == n0 + 1


def test_executor_names_map_dashes():
    f = P._named(lambda ops, x: x, "spmv", "sell", "pallas-interpret")
    assert f.__name__ == "spmv_sell_pallas_interpret"


# --- plan spans ---------------------------------------------------------------

def test_an_auto_compile_adds_to_each_plan_span_once():
    m = holstein_hubbard_surrogate(2_000, seed=3, dtype=np.float32)
    t0 = _plan_totals()
    p = SpMVPlan.compile(m, PlanConfig(format="auto"))
    t1 = _plan_totals()
    assert p.report.format != "csr"
    for k in t0:
        assert t1[k][0] > t0[k][0] and t1[k][1] > t0[k][1], k
    assert SpMVPlan.compile(m, PlanConfig(format="auto")) is p
    assert _plan_totals() == t1


def test_plan_spans_never_nest():
    t0 = _plan_totals()
    with P._plan_span("plan.select"):
        with P._plan_span("plan.convert"):
            with P._plan_span("plan.build"):
                pass
    t1 = _plan_totals()
    assert t1["plan.select"][0] == t0["plan.select"][0] + 1
    assert t1["plan.convert"] == t0["plan.convert"] and t1["plan.build"] == t0["plan.build"]
    with P._plan_span("plan.build"):   # closed spans leave none open
        pass
    assert _plan_totals()["plan.build"][0] == t0["plan.build"][0] + 1
