"""The readers of the program's spans: device idle per Lanczos step inside
the ``lanczos`` spans of a trace, and the plan spans' in-process totals."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common  # noqa: E402
from bench.trace import Device, Summary  # noqa: E402

MS = 1e6   # ns


def _reader(name):
    return common.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


def _summary(main=True, devices=1):
    """One traced solve (0-30 ms) holding one Lanczos attempt (1-21 ms) of
    two steps; programs run 2-5, 7-10, 12-15 and 16-19 ms, and 24-26 ms
    after the attempt.  Idle inside the attempt: 20 - 12 = 8 ms."""
    progs = [("jit_spmv_hybrid_xla", 2 * MS, 5 * MS), ("jit_vdot", 7 * MS, 10 * MS),
             ("jit_spmv_hybrid_xla", 12 * MS, 15 * MS), ("jit_norm", 16 * MS, 19 * MS),
             ("jit_eigh", 24 * MS, 26 * MS)]
    host = [("solve", 0.0, 30 * MS, 0, main),
            ("lanczos", 1 * MS, 21 * MS, 1, main),
            ("lanczos.step", 1 * MS, 11 * MS, 2, main),
            ("lanczos.sync", 5 * MS, 11 * MS, 3, main),
            ("lanczos.step", 11 * MS, 21 * MS, 2, main),
            ("lanczos.sync", 15 * MS, 21 * MS, 3, main)]
    return Summary([Device(f"/device:TPU:{i}", list(progs), 0.0) for i in range(devices)],
                   host)


def _ctx(summary, steps=2, window=(0.0, 30 * MS)):
    return SimpleNamespace(trace_summary=summary, trace_window=window,
                           window={"steps": steps})


def test_step_idle_is_the_idle_inside_the_lanczos_spans_per_step():
    read = _reader("step_idle_ms.solve").read
    assert read(_ctx(_summary())) == pytest.approx(4.0)
    assert read(_ctx(_summary(devices=2))) == pytest.approx(4.0)
    # a trace that marks no main thread: the spans of every thread count
    assert read(_ctx(_summary(main=False))) == pytest.approx(4.0)


def test_step_idle_reads_the_main_thread_where_the_trace_marks_it():
    s = _summary()
    s.host += [("lanczos", 21 * MS, 29 * MS, 0, False),
               ("lanczos.step", 21 * MS, 29 * MS, 1, False)]
    assert _reader("step_idle_ms.solve").read(_ctx(s)) == pytest.approx(4.0)


def test_step_idle_is_none_unless_the_step_spans_count_the_window_steps():
    read = _reader("step_idle_ms.solve").read
    assert read(_ctx(_summary(), steps=3)) is None
    assert read(_ctx(_summary(), steps=0)) is None
    # a program without spans (the parent of this metric)
    bare = _summary()
    bare.host = [h for h in bare.host if not h[0].startswith("lanczos")]
    assert read(_ctx(bare)) is None
    assert read(_ctx(None)) is None
    assert read(_ctx(Summary([], _summary().host))) is None
    # steps that start outside the traced window are not the window's
    assert read(_ctx(_summary(), window=(0.0, 10 * MS))) is None


@pytest.mark.parametrize("name,span", [("plan_select_s", "plan.select"),
                                       ("plan_convert_s", "plan.convert"),
                                       ("plan_build_s", "plan.build")])
def test_plan_readers_take_the_span_totals(monkeypatch, name, span):
    from repro.utils import spans
    snap = {"spans": {"plan.select": [3, 5.5], "plan.convert": [1, 6.25],
                      "plan.build": [1, 1.125], "lanczos": [2, 26.0]},
            "counters": {}}
    monkeypatch.setattr(spans, "snapshot", lambda: snap)
    assert _reader(name).read(_ctx(None)) == snap["spans"][span][1]
    monkeypatch.setattr(spans, "snapshot", lambda: {"spans": {}, "counters": {}})
    assert _reader(name).read(_ctx(None)) is None


@pytest.mark.parametrize("name", ["plan_select_s", "plan_convert_s", "plan_build_s"])
def test_plan_readers_read_nothing_from_a_program_without_spans(monkeypatch, name):
    import repro.utils
    monkeypatch.setitem(sys.modules, "repro.utils.spans", None)   # import fails
    monkeypatch.delattr(repro.utils, "spans", raising=False)
    with pytest.raises(ImportError):
        from repro.utils import spans  # noqa: F401
    assert _reader(name).read(_ctx(None)) is None
