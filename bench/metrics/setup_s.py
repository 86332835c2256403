"""setup_s: process start until the window opens (build, plan, compile,
device put, warm-up), on the host clock."""


def read(ctx):
    return ctx.timings.get("setup_s")
