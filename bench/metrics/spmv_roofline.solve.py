"""spmv_roofline.solve: compulsory SpMV bytes over (chips x peak HBM
bandwidth) over the device time of one SpMV call, in %; on several chips
the busiest device's time.  The SpMV's programs are those that started
inside the runner's bare call of the plan (``readers.SPMV_PROBE``); they
are read only where each ran once per SpMV call of the traced solves."""
from bench import compulsory
from bench.readers import family_time, probed_families


def read(ctx):
    calls = ctx.window.get("spmv_calls")
    t_ns = family_time(ctx, calls, probed_families(ctx)) if calls else None
    if not t_ns:
        return None
    op = ctx.operator
    b = compulsory.spmv_bytes(op.n, op.n, op.nnz, op.dtype, op.stored_values, op.n_diag)
    return 100.0 * b * calls / (ctx.chips * ctx.peaks["hbm_bytes_per_s"]) / (t_ns * 1e-9)
