"""plan_select_s: host seconds in the program's ``plan.select`` spans
(format and backend selection: ``perfmodel.select_format``,
``registry.select_backend``), from its in-process span totals of this run;
nothing from a program without spans."""


def read(ctx):
    try:
        from repro.utils import spans
    except ImportError:
        return None
    got = spans.snapshot()["spans"].get("plan.select")
    return got[1] if got else None
