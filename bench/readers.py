"""Arithmetic that several metric readers share."""
from __future__ import annotations

# the host annotation around the runner's one bare call of the timed SpMV,
# made in the traced run before its window: the programs that start on the
# device inside it are the SpMV's, whatever the program names them
SPMV_PROBE = "spmv_probe"


def busy_ns(ctx):
    """Device busy ns in the traced window (the union of program
    executions), averaged over the devices; None without a trace."""
    s, w = ctx.trace_summary, ctx.trace_window
    if s is None or not s.devices or w is None or w[1] <= w[0]:
        return None
    return sum(s.busy_ns(d, *w) for d in s.devices) / len(s.devices)


def idle_share(ctx):
    """1 - busy / window over the traced window, averaged over devices, %."""
    busy = busy_ns(ctx)
    if busy is None:
        return None
    w = ctx.trace_window
    return 100.0 * (1.0 - busy / (w[1] - w[0]))


def probed_families(ctx, annotation: str = SPMV_PROBE):
    """The program families that started on any device inside the host
    annotation ``annotation``; None without a trace or the annotation."""
    s = ctx.trace_summary
    w = s.annotation_window(annotation) if s is not None else None
    if w is None:
        return None
    return set().union(*(s.families(d, *w) for d in s.devices))


def family_time(ctx, calls: int, families):
    """Device ns of the named program families in the traced window, the
    busiest device's.  None unless every one of them ran exactly ``calls``
    times there on every device: then the families are not the work that
    ran once per call, and no time is read rather than another program's."""
    s, w = ctx.trace_summary, ctx.trace_window
    if s is None or not s.devices or w is None or not families:
        return None
    worst = 0.0
    for dev in s.devices:
        fams = s.families(dev, *w)
        if any(fams.get(f, (0, 0.0))[0] != calls for f in families):
            return None
        worst = max(worst, sum(fams[f][1] for f in families))
    return worst
