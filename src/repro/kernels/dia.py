"""DIA kernels (dense secondary diagonals: stride-1, zero index traffic).

Registry entries: ``(dia, {spmv, spmm}, {xla, loop_reference})`` plus the
Pallas SpMV (``dia_spmv.py``'s shifted-window kernel) under
``{pallas, pallas_interpret}``.

The XLA entries stream each stored diagonal against a shifted slice of the
zero-padded input (``y = Σ_k data[k] * x[i + offsets[k]]``), one diagonal
per step of a loop: each slice starts at ``pad0 + offsets[k]``, a
per-container constant, so the program reads ``data`` and ``x`` with
stride-1 dynamic slices, gathers nothing, and does not grow with the
number of diagonals.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.formats import DIA
from ..utils import hw, spans
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

register_stat("dia_pallas_prepare")


def shifted_slices(m: DIA) -> tuple:
    """The geometry of the XLA executors, once per container: ``(pad0,
    pad1, n, table)`` — the zeros padded before and after ``x`` so that
    every slice ``[pad0 + off, pad0 + off + n)`` of a diagonal that meets
    the matrix lies inside it, the row count, and the int32 ``(2, nd)``
    table of those diagonals' indices and slice starts.  A container with
    a diagonal to stream counts once under ``dia.shifted_slices``."""
    geo = getattr(m, "_shifted_slices", None)
    if geo is None:
        n, ncols = m.shape
        offs = [int(o) for o in np.asarray(m.offsets)]
        diags = [k for k, o in enumerate(offs) if -n < o < ncols]
        kept = [offs[k] for k in diags]
        pad0 = max([0] + [-o for o in kept])
        pad1 = max([0] + [o + n - ncols for o in kept])
        table = np.asarray([diags, [pad0 + o for o in kept]], np.int32).reshape(2, -1)
        geo = (pad0, pad1, n, table)
        object.__setattr__(m, "_shifted_slices", geo)
        if diags:
            spans.count("dia.shifted_slices")
    return geo


def _operands(m: DIA) -> tuple:
    return m.data, m.scale, shifted_slices(m)[-1]


def dia_shifted_arrays(geo: tuple, ops, x: jnp.ndarray) -> jnp.ndarray:
    """``y[i] = Σ_k data[k, i] * x[i + offsets[k]]`` for ``x`` of shape
    ``(ncols,)`` or a ``(ncols, K)`` block: a loop over the table's
    diagonals, each against a shifted slice (along axis 0) of ``x`` padded
    with zeros.  Slots of a diagonal that run off the matrix meet the
    padding, so they add nothing.  Quantized containers carry a
    per-diagonal fp32 scale, applied to each diagonal before its product."""
    pad0, pad1, n, _ = geo
    data, scale, table = ops
    shape = (n,) + x.shape[1:]
    if table.shape[1] == 0:
        return jnp.zeros(shape, dtype=x.dtype)
    acc = acc_dtype(data.dtype, x.dtype)
    table = jnp.asarray(table)
    xp = jnp.pad(x.astype(acc), [(pad0, pad1)] + [(0, 0)] * (x.ndim - 1))

    def step(j, y):
        k = table[0, j]
        d = jax.lax.dynamic_index_in_dim(data, k, keepdims=False)[:n].astype(acc)
        if scale is not None:
            d = d * jax.lax.dynamic_index_in_dim(scale, k, keepdims=False).astype(acc)
        xs = jax.lax.dynamic_slice_in_dim(xp, table[1, j], n, axis=0)
        return y + d.reshape((n,) + (1,) * (x.ndim - 1)) * xs

    return jax.lax.fori_loop(0, table.shape[1], step, jnp.zeros(shape, acc))


def dia_spmv(m: DIA, x: jnp.ndarray) -> jnp.ndarray:
    """``m @ x`` for a vector or an ``(ncols, K)`` block (``dia_spmm``)."""
    return dia_shifted_arrays(shifted_slices(m), _operands(m), x)


dia_spmm = dia_spmv


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
                 description="shifted slices of a zero-padded x, no gather")
def _build_spmv(m: DIA, ctx) -> CompiledKernel:
    geo = shifted_slices(m)
    return CompiledKernel(lambda ops, x: dia_shifted_arrays(geo, ops, x), "xla",
                          operands=to_device(m, *_operands(m)))


@register_kernel("dia", "spmm", "xla",
                 description="shifted row slices of a zero-padded block")
def _build_spmm(m: DIA, ctx) -> CompiledKernel:
    return _build_spmv(m, ctx)   # the same slices, along axis 0 of the block


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
