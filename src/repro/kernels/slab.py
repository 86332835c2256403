"""Distributed slab multiplies: the shard executors' inner kernels.

``core.distributed_plan`` packs each device's row partition as either a
padded 2-D ELL slab or a flat SELL-C slab and runs one multiply per column
block inside ``shard_map``.  Those inner multiplies used to be inlined in
the executor builder; they are registry entries now — ``(slab_ell |
slab_sell, {spmv, spmm}, {xla, loop_reference})`` — so the distributed
planner dispatches through the same table as the local plans (and the
parity suite validates the slab kernels like any other entry).

The operand here is a :class:`SlabMeta` (pack + partition-local row count),
not a format container: the slab arrays themselves arrive per call, shaped
``(rows_pp, W)`` (ell) or ``(L,)`` (sell flat), with ``x`` either ``(n,)``
or ``(n, K)`` — one closure serves the SpMV and SpMM executors.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .accum import acc_dtype
from .registry import CompiledKernel, closure_kernel, register_kernel


@dataclass(frozen=True)
class SlabMeta:
    """What a slab-kernel build hook needs to know about the partition."""

    pack: str       # "ell" | "sell"
    rows_pp: int    # padded rows per partition (result tile height)

    #: registry cost hooks key on nnz; slabs are pre-balanced per shard
    nnz = 1


def _ell_mult(rows_pp: int):
    def mult(colb, valb, ridb, x):
        acc = acc_dtype(valb.dtype, x.dtype)
        g = jnp.take(x, colb, axis=0).astype(acc)  # (rows_pp, W[, K])
        v = valb.astype(acc)
        if x.ndim == 1:
            return jnp.sum(v * g, axis=1)
        return jnp.sum(v[..., None] * g, axis=1)
    return mult


def _sell_mult(rows_pp: int):
    def mult(colb, valb, ridb, x):
        acc = acc_dtype(valb.dtype, x.dtype)
        g = jnp.take(x, colb, axis=0).astype(acc)  # (L[, K])
        v = valb.astype(acc)
        prod = v * g if x.ndim == 1 else v[:, None] * g
        return jax.ops.segment_sum(prod, ridb, num_segments=rows_pp + 1)[:rows_pp]
    return mult


def _ell_mult_loop(rows_pp: int):
    """Loop oracle: one pass per slab width column."""
    def mult(colb, valb, ridb, x):
        W = colb.shape[1]
        acc = acc_dtype(valb.dtype, x.dtype)
        v = valb.astype(acc)
        shape = (rows_pp,) if x.ndim == 1 else (rows_pp, x.shape[1])
        y = jnp.zeros(shape, dtype=acc)
        for j in range(W):
            g = jnp.take(x, colb[:, j], axis=0).astype(acc)
            y = y + (v[:, j] * g if x.ndim == 1 else v[:, j, None] * g)
        return y
    return mult


def _sell_mult_loop(rows_pp: int):
    """Loop oracle: scatter-add over partition-local row ids (independent
    of the segment-sum formulation it validates)."""
    def mult(colb, valb, ridb, x):
        acc = acc_dtype(valb.dtype, x.dtype)
        g = jnp.take(x, colb, axis=0).astype(acc)
        v = valb.astype(acc)
        prod = v * g if x.ndim == 1 else v[:, None] * g
        shape = (rows_pp + 1,) if x.ndim == 1 else (rows_pp + 1, x.shape[1])
        y = jnp.zeros(shape, dtype=prod.dtype)
        return y.at[ridb].add(prod)[:rows_pp]
    return mult


#: slab entries are ranked only against their own loop oracle, so flat
#: nominal costs (xla always preferred) replace the roofline hooks
def _const_cost(seconds: float):
    return lambda meta, ctx: seconds


_BUILDERS = {
    ("ell", "xla"): _ell_mult,
    ("sell", "xla"): _sell_mult,
    ("ell", "loop_reference"): _ell_mult_loop,
    ("sell", "loop_reference"): _sell_mult_loop,
}

for _pack in ("ell", "sell"):
    for _backend in ("xla", "loop_reference"):
        for _op in ("spmv", "spmm"):
            def _make(pack=_pack, backend=_backend):
                def build(meta: SlabMeta, ctx) -> CompiledKernel:
                    fn = _BUILDERS[(pack, backend)](meta.rows_pp)
                    return closure_kernel(fn, "xla" if backend == "xla" else "loop")
                return build
            register_kernel(
                f"slab_{_pack}", _op, _backend,
                auto=_backend == "xla",
                cost=_const_cost(0.0 if _backend == "xla" else 1.0),
                description=("partition-local %s slab multiply%s" % (
                    _pack, "" if _backend == "xla" else " (oracle)")),
            )(_make())


def slab_mult(pack: str, rows_pp: int, backend: str = "xla",
              op: str = "spmv"):
    """Build the shard-local multiply for one slab pack through the registry
    (the distributed executors' dispatch point).  ``op`` selects the table
    row — today spmv/spmm share builders (x's rank dispatches), but the
    executor must ask for the op it runs so a future fused SpMM entry is
    actually picked up."""
    from . import registry as R
    return R.build(SlabMeta(pack, rows_pp), f"slab_{pack}", op, backend).fn
