"""build_s: host seconds inside the program's operator generator."""


def read(ctx):
    return ctx.timings.get("build_s")
