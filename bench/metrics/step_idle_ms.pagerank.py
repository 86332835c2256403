"""step_idle_ms.pagerank: device idle ms per PageRank iteration.  The
complement of program executions inside the program's ``pagerank`` spans
in the traced window, averaged over the chips used, over the
``pagerank.step`` spans there.  Spans on the main host thread are taken
where the trace marks that thread, else the spans of every thread.  Read
only where the step spans count the window's iterations (nothing from a
program without spans)."""
from bench.trace import clip, union


def _spans(s, name, t0, t1):
    found = [(a, b, main) for n, a, b, _, main in s.host if n == name and t0 <= a < t1]
    return [(a, b) for a, b, main in found if main] or [(a, b) for a, b, _ in found]


def read(ctx):
    s, w, steps = ctx.trace_summary, ctx.trace_window, ctx.window.get("steps")
    if s is None or not s.devices or w is None or not steps:
        return None
    if len(_spans(s, "pagerank.step", *w)) != steps:
        return None
    solves = clip(union(_spans(s, "pagerank", *w)), *w)
    idle = sum((b - a) - s.busy_ns(d, a, b) for d in s.devices for a, b in solves)
    return idle / len(s.devices) / steps * 1e-6
