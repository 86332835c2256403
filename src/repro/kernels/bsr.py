"""BSR kernels (MXU-native dense blocks).

Registry entries: ``(bsr, {spmv, spmm}, {xla, loop_reference, pallas,
pallas_interpret})``.  The Pallas entries wrap the BELL scalar-prefetch
kernel of ``bsr_spmm.py`` (SpMV rides the SpMM kernel through a lane-padded
column panel, as the roofline model charges it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import formats as F
from ..core.formats import BSR
from . import bsr_spmm as KP
from .accum import acc_dtype
from .cache import cached, is_traced, register_stat, to_device
from .registry import (
    CAP_OK,
    FLOAT_PALLAS_VALUE_DTYPES,
    Capability,
    CompiledKernel,
    KernelContext,
    _probe_pallas_dtype,
    closure_kernel,
    register_kernel,
)

register_stat("bsr_block_row_ids")
register_stat("bsr_bell_pack")


def bsr_block_row_ids(m: BSR) -> jnp.ndarray:
    if is_traced(m.block_row_ptr):
        nb = m.n_blocks
        return (
            jnp.searchsorted(
                jnp.asarray(m.block_row_ptr), jnp.arange(nb, dtype=jnp.int32), side="right"
            ).astype(jnp.int32)
            - 1
        )

    def build():
        brp = np.asarray(m.block_row_ptr, dtype=np.int64)
        return np.repeat(np.arange(len(brp) - 1, dtype=np.int32), np.diff(brp))

    return cached(m, "_block_row_ids", "bsr_block_row_ids", build)


def _operands(m: BSR) -> tuple:
    return m.blocks, m.block_col_idx, bsr_block_row_ids(m), m.scale


def bsr_spmv_arrays(ops, x: jnp.ndarray, shape: tuple, block_shape: tuple):
    blocks, bci, rows, scale = ops  # blocks (nb, bm, bn)
    bm, bn = block_shape
    acc = acc_dtype(blocks.dtype, x.dtype)
    xb = jnp.take(x.reshape(-1, bn), bci, axis=0)  # (nb, bn)
    partial = jnp.einsum("kmn,kn->km", jnp.asarray(blocks).astype(acc),
                         xb.astype(acc))  # (nb, bm)
    if scale is not None:  # per-block dequant scale on the block partials
        partial = partial * jnp.asarray(scale).astype(acc)[:, None]
    ybl = jax.ops.segment_sum(partial, rows, num_segments=shape[0] // bm)
    return ybl.reshape(-1)


def bsr_spmm_arrays(ops, X: jnp.ndarray, shape: tuple, block_shape: tuple):
    """Block-sparse matrix times dense matrix: each block feeds the MXU."""
    blocks, bci, rows, scale = ops
    bm, bn = block_shape
    acc = acc_dtype(blocks.dtype, X.dtype)
    Xb = jnp.take(X.reshape(-1, bn, X.shape[1]), bci, axis=0)  # (nb, bn, K)
    partial = jnp.einsum("kmn,knj->kmj", jnp.asarray(blocks).astype(acc),
                         Xb.astype(acc))  # (nb, bm, K)
    if scale is not None:
        partial = partial * jnp.asarray(scale).astype(acc)[:, None, None]
    ybl = jax.ops.segment_sum(partial, rows, num_segments=shape[0] // bm)
    return ybl.reshape(shape[0], X.shape[1])


def bsr_spmv(m: BSR, x: jnp.ndarray) -> jnp.ndarray:
    return bsr_spmv_arrays(_operands(m), x, m.shape, m.block_shape)


def bsr_spmm(m: BSR, X: jnp.ndarray) -> jnp.ndarray:
    return bsr_spmm_arrays(_operands(m), X, m.shape, m.block_shape)


def bell_pack(m: BSR):
    """BELL (block-ELL) host-side pack, cached once per container."""
    return cached(m, "_bell_pack", "bsr_bell_pack", lambda: KP.bsr_to_bell(m))


def bsr_spmm_slotloop(m: BSR, X: jnp.ndarray) -> jnp.ndarray:
    """Loop-reference oracle: one pass per BELL block-column slot (the
    block-granular jagged-diagonal traversal; padded slots are zero).
    Quantized containers are dequantized up front — the BELL pack reorders
    blocks into slots, losing the per-block scale alignment."""
    if m.scale is not None:
        m = F.dequantize(m)
    bcols, slab = bell_pack(m)
    bm, bk = m.block_shape
    nbr, nbpp = bcols.shape
    Xb = X.reshape(-1, bk, X.shape[1])
    Y = jnp.zeros((nbr, bm, X.shape[1]),
                  dtype=acc_dtype(np.asarray(slab).dtype, X.dtype))
    bc = jnp.asarray(bcols)
    sl = jnp.asarray(slab)
    for j in range(nbpp):
        Xj = jnp.take(Xb, bc[:, j], axis=0)              # (nbr, bk, K)
        Y = Y + jnp.einsum("rmk,rkj->rmj", sl[:, j], Xj)
    return Y.reshape(nbr * bm, X.shape[1])[: m.shape[0]]


# --- registry entries -------------------------------------------------------


@register_kernel("bsr", "spmv", "xla",
                 description="block gather + per-block einsum + segment-sum")
def _build_spmv(m: BSR, ctx) -> CompiledKernel:
    return CompiledKernel(
        functools.partial(bsr_spmv_arrays, shape=m.shape,
                          block_shape=m.block_shape),
        "xla", operands=to_device(m, *_operands(m)))


@register_kernel("bsr", "spmm", "xla",
                 description="multi-vector block einsum + segment-sum")
def _build_spmm(m: BSR, ctx) -> CompiledKernel:
    return CompiledKernel(
        functools.partial(bsr_spmm_arrays, shape=m.shape,
                          block_shape=m.block_shape),
        "xla", operands=to_device(m, *_operands(m)))


@register_kernel("bsr", "spmv", "loop_reference", auto=False,
                 description="BELL slot-loop oracle (single column)")
def _build_spmv_loop(m: BSR, ctx) -> CompiledKernel:
    return closure_kernel(lambda x: bsr_spmm_slotloop(m, x[:, None])[:, 0],
                          "loop")


@register_kernel("bsr", "spmm", "loop_reference", auto=False,
                 description="BELL slot-loop oracle")
def _build_spmm_loop(m: BSR, ctx) -> CompiledKernel:
    return closure_kernel(lambda X: bsr_spmm_slotloop(m, X), "loop")


def _probe_bell(m, ctx: KernelContext) -> Capability:
    """The BELL kernel scalar-prefetches its whole (block-rows, slots)
    column table into SMEM; a table that cannot fit is refused to compile."""
    cap = _probe_pallas_dtype(m, ctx)
    if not cap.ok or m is None:
        return cap
    brp = np.asarray(m.block_row_ptr)
    nbpp = int(max(1, np.diff(brp).max())) if len(brp) > 1 else 1
    table = KP.bell_table_smem_bytes(len(brp) - 1, nbpp)
    if table > ctx.chip.smem_bytes - KP.SMEM_RESERVE_BYTES:
        return Capability(False, f"the BELL block-column table ({table} B "
                                 "scalar-prefetched) does not fit the SMEM")
    return CAP_OK


def _build_bell_spmm(m: BSR, ctx: KernelContext, interpret: bool) -> CompiledKernel:
    if m.scale is not None:  # probe should have rejected; belt-and-braces
        m = F.dequantize(m)
    bcols, slab = bell_pack(m)
    M = m.shape[0]
    label = "pallas-interpret" if interpret else "pallas"

    def kernel(ops, X):
        bc, bl = ops
        return KP.bell_spmm_arrays(bc, bl, X, interpret=interpret)[:M]

    return CompiledKernel(kernel, label, operands=to_device(m, bcols, slab))


@register_kernel("bsr", "spmm", "pallas", probe=_probe_bell,
                 description="BELL scalar-prefetch MXU kernel",
                 value_dtypes=FLOAT_PALLAS_VALUE_DTYPES)
def _build_bell_compiled(m: BSR, ctx) -> CompiledKernel:
    return _build_bell_spmm(m, ctx, interpret=False)


@register_kernel("bsr", "spmm", "pallas_interpret", probe=_probe_bell,
                 description="BELL scalar-prefetch kernel via the interpreter",
                 value_dtypes=FLOAT_PALLAS_VALUE_DTYPES)
def _build_bell_interpret(m: BSR, ctx) -> CompiledKernel:
    return _build_bell_spmm(m, ctx, interpret=True)


def _build_bell_spmv(m: BSR, ctx: KernelContext, interpret: bool) -> CompiledKernel:
    ck = _build_bell_spmm(m, ctx, interpret)
    lane = 8  # thin N=1 is MXU-hostile; the model charges the padded panel

    def kernel(ops, x):
        return ck.kernel(ops, jnp.tile(x[:, None], (1, lane)))[:, 0]

    return CompiledKernel(kernel, ck.label, operands=ck.operands)


@register_kernel("bsr", "spmv", "pallas", probe=_probe_bell,
                 description="BELL kernel over a lane-padded column panel",
                 value_dtypes=FLOAT_PALLAS_VALUE_DTYPES)
def _build_bell_spmv_compiled(m: BSR, ctx) -> CompiledKernel:
    return _build_bell_spmv(m, ctx, interpret=False)


@register_kernel("bsr", "spmv", "pallas_interpret", probe=_probe_bell,
                 description="lane-padded BELL panel via the interpreter",
                 value_dtypes=FLOAT_PALLAS_VALUE_DTYPES)
def _build_bell_spmv_interpret(m: BSR, ctx) -> CompiledKernel:
    return _build_bell_spmv(m, ctx, interpret=True)
