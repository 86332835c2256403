"""DIA kernels (dense secondary diagonals: stride-1, zero index traffic).

Registry entries: ``(dia, {spmv, spmm}, {xla, loop_reference})`` plus the
Pallas SpMV (``dia_spmv.py``'s shifted-window kernel) under
``{pallas, pallas_interpret}``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.formats import DIA
from ..utils import hw
from . import dia_spmv as KP
from .accum import acc_dtype
from .cache import cached, register_stat, spmm_by_columns, to_device
from .registry import (
    CAP_OK,
    Capability,
    CompiledKernel,
    KernelContext,
    _probe_pallas_dtype,
    closure_kernel,
    register_kernel,
)

register_stat("dia_gather_tables")
register_stat("dia_pallas_prepare")


def dia_gather_tables(m: DIA):
    """Padded shift-gather tables: idx[k, i] = i + offsets[k] clipped into
    range, data masked to zero where the shift runs off the matrix.  One
    (nd, n) gather then replaces the per-diagonal dynamic_slice chain."""

    def build():
        n, ncols = m.shape
        offs = np.asarray(m.offsets, dtype=np.int64)
        i = np.arange(n, dtype=np.int64)
        idx = i[None, :] + offs[:, None]                      # (nd, n)
        valid = (idx >= 0) & (idx < ncols)
        idx = np.clip(idx, 0, max(0, ncols - 1))
        # np.where, not * valid: bool multiply is undefined for ml_dtypes fp8
        d = np.asarray(m.data)[:, :n]
        data = np.where(valid, d, np.zeros((), dtype=d.dtype))
        return idx.astype(np.int32), data

    return cached(m, "_gather_tables", "dia_gather_tables", build)


def _operands(m: DIA) -> tuple:
    idx, data = dia_gather_tables(m)
    return idx, data, m.scale


def dia_spmv_arrays(ops, x: jnp.ndarray) -> jnp.ndarray:
    """Vectorized DIA: one shift-gather of shape (nd, n), one reduction.
    Quantized containers carry a per-diagonal fp32 scale, applied to the
    (nd, n) product table before the reduction over diagonals."""
    idx, data, scale = ops
    if data.shape[0] == 0:
        return jnp.zeros(data.shape[1], dtype=x.dtype)
    acc = acc_dtype(data.dtype, x.dtype)
    prod = jnp.asarray(data).astype(acc) * jnp.take(x, idx, axis=0).astype(acc)
    if scale is not None:
        prod = prod * jnp.asarray(scale).astype(acc)[:, None]
    return jnp.sum(prod, axis=0)


def dia_spmm_arrays(ops, X: jnp.ndarray) -> jnp.ndarray:
    idx, data, scale = ops
    if data.shape[0] == 0:
        return jnp.zeros((data.shape[1], X.shape[1]), dtype=X.dtype)
    acc = acc_dtype(data.dtype, X.dtype)
    d = jnp.asarray(data).astype(acc)
    if scale is not None:
        d = d * jnp.asarray(scale).astype(acc)[:, None]
    return jnp.einsum("kn,knj->nj", d, jnp.take(X, idx, axis=0).astype(acc))


def dia_spmv(m: DIA, x: jnp.ndarray) -> jnp.ndarray:
    return dia_spmv_arrays(_operands(m), x)


def dia_spmm(m: DIA, X: jnp.ndarray) -> jnp.ndarray:
    return dia_spmm_arrays(_operands(m), X)


def dia_spmv_loop(m: DIA, x: jnp.ndarray) -> jnp.ndarray:
    """One shifted stride-1 read per stored diagonal (static offsets) — the
    per-diagonal dynamic_slice chain, kept as the paper-fidelity oracle."""
    n, ncols = m.shape
    offsets = np.asarray(m.offsets)
    acc = acc_dtype(jnp.asarray(m.data).dtype, x.dtype)
    data = jnp.asarray(m.data).astype(acc)
    scale = None if m.scale is None else np.asarray(m.scale, dtype=np.float64)
    y = jnp.zeros(n, dtype=acc)
    for k, off in enumerate(offsets.tolist()):
        lo = max(0, -off)
        hi = min(n, ncols - off)
        if hi <= lo:
            continue
        contrib = data[k, lo:hi] * jax.lax.dynamic_slice(x, (lo + off,), (hi - lo,)).astype(acc)
        if scale is not None:
            contrib = contrib * float(scale[k])
        y = y.at[lo:hi].add(contrib)
    return y


def dia_prepared(m: DIA, tile: int = KP.TILE_QUANTUM):
    """Host-side Pallas padding (``dia_spmv.dia_prepare``), cached once per
    (container, tile)."""
    return cached(m, f"_dia_prepared_{tile}", "dia_pallas_prepare",
                  lambda: KP.dia_prepare(m, tile))


# --- registry entries -------------------------------------------------------


@register_kernel("dia", "spmv", "xla",
                 description="one (nd, n) shift-gather + reduction")
def _build_spmv(m: DIA, ctx) -> CompiledKernel:
    return CompiledKernel(dia_spmv_arrays, "xla",
                          operands=to_device(m, *_operands(m)))


@register_kernel("dia", "spmm", "xla",
                 description="multi-vector shift-gather einsum")
def _build_spmm(m: DIA, ctx) -> CompiledKernel:
    return CompiledKernel(dia_spmm_arrays, "xla",
                          operands=to_device(m, *_operands(m)))


@register_kernel("dia", "spmv", "loop_reference", auto=False,
                 description="per-diagonal dynamic_slice chain oracle")
def _build_spmv_loop(m: DIA, ctx) -> CompiledKernel:
    return closure_kernel(lambda x: dia_spmv_loop(m, x), "loop")


@register_kernel("dia", "spmm", "loop_reference", auto=False,
                 description="column-by-column per-diagonal chains")
def _build_spmm_loop(m: DIA, ctx) -> CompiledKernel:
    return closure_kernel(spmm_by_columns(lambda x: dia_spmv_loop(m, x)),
                          "loop")


def _pallas_claim(m: DIA, tile: int) -> int:
    """VMEM claim of the Pallas kernel's tiling for this container."""
    offsets = np.asarray(m.offsets)
    n_pad = -(-m.shape[0] // tile) * tile
    pad0 = max(0, -int(offsets.min()))
    rows = KP.x_rows(pad0, n_pad, int(offsets.max()))
    vb = int(np.dtype(np.asarray(m.data).dtype).itemsize)
    return KP.vmem_bytes(len(offsets), tile, rows, vb)


def _probe_dia_pallas(m, ctx: KernelContext) -> Capability:
    cap = _probe_pallas_dtype(m, ctx)
    if not cap.ok or m is None:
        return cap
    if int(np.asarray(m.offsets).shape[0]) == 0:
        return Capability(False, "no stored diagonals (empty DIA)")
    tile = ctx.tile or KP.TILE_QUANTUM
    if tile % KP.TILE_QUANTUM:
        return Capability(False, f"tile {tile} is not a multiple of "
                                 f"{KP.TILE_QUANTUM}")
    if not hw.vmem_fits(_pallas_claim(m, tile), ctx.chip):
        return Capability(False, "diagonal slab + padded x exceed the VMEM budget")
    return CAP_OK


def _build_dia_pallas(m: DIA, ctx: KernelContext, interpret: bool) -> CompiledKernel:
    tile = ctx.tile or KP.TILE_QUANTUM
    data, pad0, rows, offsets, n = dia_prepared(m, tile)
    label = "pallas-interpret" if interpret else "pallas"
    if not offsets:
        return closure_kernel(lambda x: jnp.zeros(n, dtype=x.dtype), label)
    # per-diagonal scales ride into the kernel as a static float tuple,
    # exactly like the offsets (both are per-diagonal compile-time facts)
    scales = None if m.scale is None else tuple(
        float(v) for v in np.asarray(m.scale, dtype=np.float64))
    limit = hw.vmem_limit(_pallas_claim(m, tile))
    odt = acc_dtype(data.dtype, np.float32)

    def kernel(ops, x):
        (dataj,) = ops
        y = KP.dia_spmv_arrays(dataj, KP.pad_x(x, pad0, rows, odt),
                               offsets=offsets, tile=tile, pad0=pad0,
                               interpret=interpret, scales=scales,
                               vmem_limit=limit)
        return y.reshape(-1)[:n]

    return CompiledKernel(kernel, label, operands=to_device(m, data))


@register_kernel("dia", "spmv", "pallas", probe=_probe_dia_pallas,
                 description="shifted-window tile kernel, static offsets")
def _build_dia_pallas_compiled(m: DIA, ctx) -> CompiledKernel:
    return _build_dia_pallas(m, ctx, interpret=False)


@register_kernel("dia", "spmv", "pallas_interpret", probe=_probe_dia_pallas,
                 description="shifted-window tile kernel via the interpreter")
def _build_dia_pallas_interpret(m: DIA, ctx) -> CompiledKernel:
    return _build_dia_pallas(m, ctx, interpret=True)
