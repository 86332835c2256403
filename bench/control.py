"""The control and the planted faults that ``correct`` has to catch.

The control is the plain reference put in the program's place one
precision step down: a CSR product with the values and the vector rounded
to bfloat16 and the sums in float32, run on the device.  The faults break
the timed path underneath a run: an SpMV that returns its input (the
state unchanged), and an answer altered where it is produced.
``bench/calibrate.py`` reads them on the chip; the tests under
``tests/bench`` read them at small sizes.
"""
from __future__ import annotations

import numpy as np

from bench.placement import compile_plan


def round_bf16(x):
    """Round float32 to the nearest bfloat16 (ties to even), kept in
    float32.  Done on the bits: the compiler may drop a float32 -> bfloat16
    -> float32 round trip as excess precision, and did on the v5e."""
    import jax.numpy as jnp
    from jax import lax
    b = lax.bitcast_convert_type(x, jnp.uint32)
    b = (b + jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    return lax.bitcast_convert_type(b, jnp.float32)


def _bf16_csr(host):
    import jax.numpy as jnp

    from bench.reference import to_bf16
    a = host.a64.tocoo()
    rows = jnp.asarray(a.row.astype(np.int32))
    cols = jnp.asarray(a.col.astype(np.int32))
    vals = jnp.asarray(to_bf16(a.data))
    return rows, cols, vals


def bf16_reference_spmv(host):
    """y = bf16(A) @ bf16(x), float32 sums, on the device."""
    import jax
    rows, cols, vals = _bf16_csr(host)
    n = host.n

    @jax.jit
    def bench_bf16_spmv(x):
        return jax.ops.segment_sum(vals * round_bf16(x)[cols], rows, num_segments=n)
    return bench_bf16_spmv


def plan_with(kind: str, host=None, value_dtype: str | None = None):
    """A ``compile_plan`` for the Lanczos runner's ``env`` that compiles the
    real plan and breaks it as ``kind`` says."""
    def compile_(config, matrix, devices):
        if value_dtype is not None:
            from repro.core.plan import SpMVPlan
            from repro.core.planconfig import PlanConfig

            from bench.placement import chip_spec
            plan = SpMVPlan.compile(matrix, PlanConfig(
                format=config["placement"]["format"], value_dtype=value_dtype,
                chip=chip_spec(devices[0])))
            return plan, {"format": plan.report.format, "kernel": plan.report.kernel,
                          "value_dtype": value_dtype}
        plan, info = compile_plan(config, matrix, devices)
        if kind == "sound":
            return plan, info
        if kind == "bf16_reference":
            return bf16_reference_spmv(host), dict(info, control="bf16_reference")
        if kind == "state_unchanged":
            return (lambda x: x), dict(info, fault=kind)
        if kind == "answer_altered":
            return (lambda x: plan(x).at[0].add(1.0)), dict(info, fault=kind)
        raise ValueError(kind)
    return compile_
