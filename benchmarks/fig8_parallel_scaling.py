"""Fig 8: parallel SpMV scaling vs device count, per plan variant.

The paper scales OpenMP threads across sockets; the TPU analogue scales
chips.  With the distributed plan layer the figure becomes a *variant*
comparison: ``allgather`` (shared input vector, the paper's baseline),
``ring`` (shard pipeline) and ``overlap`` (local compute concurrent with
the first exchange, Schubert et al. 1106.5908) on 1..8 devices.  Per
variant we report wall time, speedup vs its own 1-device time, and the
modelled collective traffic.

Everything runs in this process over meshes cut from ``jax.devices()``
(one process per chip: a child process could not reach a chip this one
holds).  Device counts beyond what the process has are skipped; on a CPU
host, ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` provides 8.
"""
from __future__ import annotations

import time

from .common import row


def run(full: bool = False):
    import jax
    import jax.numpy as jnp

    from repro.core.distributed import make_mesh_1d
    from repro.core.distributed_plan import VARIANTS, compile_distributed_spmv_plan
    from repro.core.matrices import holstein_hubbard_surrogate

    n = 100_000 if full else 20_000
    devs = [d for d in ([1, 2, 4, 8] if full else [1, 4])
            if d <= len(jax.devices())]
    m = holstein_hubbard_surrogate(n, seed=0)
    x = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)
    rows, base = [], {}
    for d in devs:
        mesh = make_mesh_1d("data", d)
        for variant in VARIANTS:
            plan = compile_distributed_spmv_plan(m, mesh, variant=variant)
            jax.block_until_ready(plan(x))
            best = 1e9
            for _ in range(7):
                t0 = time.perf_counter()
                jax.block_until_ready(plan(x))
                best = min(best, time.perf_counter() - t0)
            if d == devs[0]:
                base[variant] = best
            rows.append(row("fig8", f"{variant}_d{d}", best * 1e3,
                            base.get(variant, best) / best,
                            plan.traffic["collective"] / 1e6, plan.slab_format))
    return rows
