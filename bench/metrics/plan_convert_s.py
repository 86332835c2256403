"""plan_convert_s: host seconds in the program's ``plan.convert`` spans
(format conversion: the miss path of ``plan._convert_cached``), from its
in-process span totals of this run; nothing from a program without spans."""


def read(ctx):
    try:
        from repro.utils import spans
    except ImportError:
        return None
    got = spans.snapshot()["spans"].get("plan.convert")
    return got[1] if got else None
