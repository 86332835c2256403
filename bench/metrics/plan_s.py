"""plan_s: host seconds compiling the plan (format selection, conversion,
packing, device put; the server's registration where it serves)."""


def read(ctx):
    return ctx.timings.get("plan_s")
