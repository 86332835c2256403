"""device_idle.solve: 1 - (union of program executions / traced window),
averaged over the chips used, in %."""
from bench.readers import idle_share


def read(ctx):
    return idle_share(ctx) if ctx.window.get("steps") else None
