"""launches_per_step.solve: device program executions in the traced window
over the Lanczos steps in it (the busiest device's count)."""


def read(ctx):
    s, win = ctx.trace_summary, ctx.window
    if s is None or not s.devices or not win.get("steps") or ctx.trace_window is None:
        return None
    return max(s.launches(d, *ctx.trace_window) for d in s.devices) / win["steps"]
