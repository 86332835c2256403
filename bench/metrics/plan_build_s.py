"""plan_build_s: host seconds in the program's ``plan.build`` spans
(executor builds: ``registry.build``, its cached tables and their device
copies), from its in-process span totals of this run; nothing from a
program without spans."""


def read(ctx):
    try:
        from repro.utils import spans
    except ImportError:
        return None
    got = spans.snapshot()["spans"].get("plan.build")
    return got[1] if got else None
