"""compile_s: JAX's backend compile seconds over set-up, persistent-cache
reads included (the copied compile clock)."""


def read(ctx):
    return ctx.timings.get("compile_s")
