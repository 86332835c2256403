"""The trace reduction of bench/trace.py, on a trace recorded on one v5e
(``fixtures/trace_1chip.xplane.pb``, written by ``bench/record_fixture.py``)
and on hand-built intervals."""
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import readers, trace  # noqa: E402
from bench.common import Context  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "trace_1chip.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace.load(FIXTURE)


def test_fixture_has_one_tpu_with_programs(summary):
    assert [d.name for d in summary.devices] == ["/device:TPU:0"]
    dev = summary.devices[0]
    assert len(dev.modules) == 78


def test_window_busy_launches_and_families(summary):
    dev = summary.devices[0]
    w = summary.annotation_window("solve")
    assert w == (45111869.0, 80784389.0)
    assert summary.launches(dev, *w) == 72
    assert summary.busy_ns(dev, *w) == pytest.approx(13585199.0)
    fams = summary.families(dev, *w)
    assert fams["jit__lambda"] == [4, 13514402.0]
    assert fams["jit_multiply"][0] == 16


def test_device_clock_is_put_on_the_host_clock(summary):
    # the v5e's device plane reads ~2.16 ms behind the host: unshifted, the
    # first solve's first SpMV would start before the solve's annotation
    dev = summary.devices[0]
    assert dev.offset_ns == -2157773.0
    # each 4-step solve holds exactly the programs it enqueued: four SpMVs
    # (the Holstein plan's jit__lambda, then the stencil's jit_kernel)
    solves = [(s, e) for n, s, e, *_ in summary.host if n == "solve"]
    assert len(solves) == 2
    first, second = (summary.families(dev, *w) for w in solves)
    assert first["jit__lambda"][0] == 4 and "jit_kernel" not in first
    assert second["jit_kernel"][0] == 4 and "jit__lambda" not in second
    assert first["jit_vdot"][0] == second["jit_vdot"][0] == 4


def test_clock_offset_is_the_least_gap_to_the_enqueue():
    enq = {1: 100.0, 2: 200.0, 3: 300.0}
    # device clock 50 behind, enqueue-to-start latencies 5, 2 and 9
    progs = [(55.0, 1), (152.0, 2), (259.0, 3), (400.0, None), (500.0, 7)]
    assert trace.clock_offset(progs, enq) == -48.0
    assert trace.clock_offset([(10.0, None)], enq) is None
    assert trace.clock_offset([], {}) is None


def test_idle_share_and_family_time_readers(summary):
    from bench.common import Cell
    cell = Cell({"name": "x", "chips": 1}, {}, {}, {}, [], [])
    ctx = Context(cell, 0, 1.0, True, device_kind="TPU v5 lite")
    ctx.trace_summary = summary
    ctx.trace_window = summary.annotation_window("solve")
    idle = readers.idle_share(ctx)
    assert idle == pytest.approx(100.0 * (1 - 13585199.0 / (80784389.0 - 45111869.0)))
    # the fixture's Holstein SpMV ran 4 times in the window
    assert readers.family_time(ctx, 4, {"jit__lambda"}) == 13514402.0
    # a family that ran another number of times is not read as the SpMV
    assert readers.family_time(ctx, 4, {"jit__lambda", "jit_multiply"}) is None
    assert readers.family_time(ctx, 12345, {"jit__lambda"}) is None
    assert readers.family_time(ctx, 4, set()) is None
    # the programs that started inside a host annotation, here the solves
    assert {"jit__lambda", "jit_multiply"} <= readers.probed_families(ctx, "solve")
    assert readers.probed_families(ctx) is None     # recorded without a probe


def _probe_summary(spmv_calls: int):
    """One device: a probe span around one SpMV program (two families),
    then a solve span with ``spmv_calls`` SpMVs and a vector program each."""
    mods = [("jit_pad", 10.0, 12.0), ("jit_spmv", 12.0, 30.0)]
    t = 100.0
    for _ in range(spmv_calls):
        mods += [("jit_pad", t, t + 2), ("jit_spmv", t + 2, t + 20),
                 ("jit_vdot", t + 20, t + 25)]
        t += 40.0
    host = [(readers.SPMV_PROBE, 5.0, 35.0, 0, True), ("solve", 90.0, t, 0, True)]
    return trace.Summary([trace.Device("/device:TPU:0", modules=mods)], host)


def test_the_spmv_is_found_by_the_probe_and_read_per_call():
    from bench.common import Cell
    cell = Cell({"name": "x", "chips": 1}, {}, {}, {}, [], [])
    ctx = Context(cell, 0, 1.0, True, device_kind="TPU v5 lite")
    ctx.trace_summary = _probe_summary(4)
    ctx.trace_window = ctx.trace_summary.annotation_window("solve")
    fams = readers.probed_families(ctx)
    assert fams == {"jit_pad", "jit_spmv"}
    assert readers.family_time(ctx, 4, fams) == 4 * (2.0 + 18.0)
    # a solve window that ran the SpMV another number of times reads nothing
    assert readers.family_time(ctx, 5, fams) is None


def test_idle_gaps_are_the_complement_of_busy(summary):
    dev = summary.devices[0]
    w = summary.annotation_window("solve")
    gaps = summary.idle_gaps(dev, *w)
    assert trace.total(gaps) + summary.busy_ns(dev, *w) == pytest.approx(w[1] - w[0])
    longest = max(gaps, key=lambda g: g[1] - g[0])
    assert summary.host_activity((longest[0] + longest[1]) / 2) != "idle"


def test_family_and_collective_names():
    assert trace.family("jit_norm(5961132016729099988)") == "jit_norm"
    assert trace.family("jit__lambda(1)") == "jit__lambda"
    assert trace.family("jit_bench_start_vector") == "jit_bench_start_vector"


def test_union_clip_subtract():
    u = trace.union([(0, 5), (3, 8), (10, 12), (12, 13), (20, 20)])
    assert u == [(0, 8), (10, 13)]
    assert trace.clip(u, 4, 11) == [(4, 8), (10, 11)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert trace.total(trace.subtract([(0, 10)], [(-1, 11)])) == 0
