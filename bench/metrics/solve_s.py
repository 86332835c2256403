"""solve_s: the window's seconds over the Lanczos solves completed in it."""


def read(ctx):
    w = ctx.window
    if ctx.trace or not w.get("solves"):
        return None
    return w["elapsed_s"] / w["solves"]
