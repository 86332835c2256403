"""Matrix-free generated-operator kernels: indices computed, never streamed.

SpMV is bandwidth-bound (paper Sec. 2-3), so every stored column index costs
4-8 B/nnz against the roofline and every stored value its dtype width.  For
the structured corpus operators -- Laplacian stencils, banded matrices, the
Holstein diagonal rule -- ``col = row + offset`` with a per-diagonal validity
rule ``lo <= row % period < hi`` regenerates both in-registers.  These
kernels consume a ``core.formats.MatrixFreeOperator`` descriptor:

* generated diagonals stream **zero** bytes (constant value folded into the
  instruction stream, index recomputed, validity applied as a reshape
  broadcast of one constant ``(period,)`` 0/1 vector);
* stored diagonals stream one dense DIA-style value lane each (still no
  index bytes: the shifted stride-1 x read *is* the index);
* matrix-boundary masking is free -- x is zero-padded so every shifted
  window is in range and out-of-matrix reads contribute exact zeros.

Registry entries: ``(matrix_free, {spmv, spmm}, {xla, loop_reference,
pallas, pallas_interpret})`` with an autotune hook for the Pallas row-tile.
Accumulation order is ascending offset = ascending column within each row,
matching the materialized-CSR loop oracle's row-major traversal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import VALUE_DTYPES, MatrixFreeOperator
from ..utils import hw
from . import dia_spmv as KD
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

register_stat("mf_tables")
register_stat("mf_pallas_prepare")


def _storage_dtype(op: MatrixFreeOperator):
    return np.dtype(VALUE_DTYPES.get(op.value_dtype, np.float32))


def _round_gen(gv: float, dtype) -> float:
    """Pre-round a generated constant through the storage dtype, so the
    in-kernel scalar is bitwise what a materialized container would stream."""
    return float(np.asarray(gv, dtype=dtype).astype(np.float64))


def mf_tables(op: MatrixFreeOperator):
    """Per-diagonal dispatch table, built once per container.

    Each entry is ``(off, spec)`` where ``spec`` is ``None`` for a stored
    lane (consumed from ``op.data`` in order) or ``(p, lo, hi, gv)`` with
    ``p = 0`` meaning "no mask needed": the rule is trivially all-rows, or
    it coincides with the matrix boundary that the zero-padded x already
    enforces for free.
    """

    def build():
        n, ncols = op.shape
        dt = _storage_dtype(op)
        diags = []
        for k, off in enumerate(op.offsets):
            gv = op.gen_values[k]
            if gv is None:
                diags.append((int(off), None))
                continue
            p, lo, hi = op.periods[k], op.los[k], op.his[k]
            trivial = lo == 0 and hi == p
            boundary = (p == n and lo == max(0, -off)
                        and hi == min(n, ncols - off))
            gvr = _round_gen(gv, dt)
            diags.append((int(off), ((0, 0, 0, gvr) if trivial or boundary
                                     else (p, lo, hi, gvr))))
        return tuple(diags)

    return cached(op, "_mf_tables", "mf_tables", build)


def _pads(op: MatrixFreeOperator, n_rows_pad: int) -> tuple[int, int]:
    """Left/right x padding so every shifted window is statically in range
    (reads past either matrix edge land on zeros -- free boundary masks)."""
    offsets = op.offsets
    pad0 = max(0, -min(offsets))
    pad1 = max(0, (n_rows_pad - 1) + max(offsets) + 1 - op.shape[1])
    return pad0, pad1


# ---------------------------------------------------------------------------
# XLA formulation: per-diagonal shifted slices of the padded x
# ---------------------------------------------------------------------------


def _rule_mask(p: int, lo: int, hi: int, dtype) -> np.ndarray:
    """The periodic rule as one constant ``(p,)`` 0/1 vector.  Detection
    only accepts periods dividing n, so ``contrib.reshape(n//p, p)`` lines
    rows up with the rule phase and a broadcast multiply applies it — no
    per-row ``i % p`` integer ops (XLA:CPU runs the fused iota-mod-compare
    an order of magnitude slower than this elementwise form), and still
    zero *streamed* pattern bytes: the vector is a trace-time constant of
    at most p elements.  Multiplying by 0 matches materialized DIA/ELL
    padding semantics (an explicit stored zero times x)."""
    i = np.arange(p)
    return ((i >= lo) & (i < hi)).astype(dtype)


def _xla_static(op: MatrixFreeOperator) -> dict:
    """The static half of the XLA executors: diagonal table, pads, dtype."""
    pad0, pad1 = _pads(op, op.shape[0])
    return dict(diags=mf_tables(op), n=op.shape[0], pad0=pad0, pad1=pad1,
                store_dtype=_storage_dtype(op))


def mf_spmv_xla(data, x: jnp.ndarray, *, diags, n, pad0, pad1,
                store_dtype) -> jnp.ndarray:
    """Vectorized matrix-free SpMV: one shifted stride-1 read per diagonal,
    reshape-broadcast rule masks, no index loads.  ``data`` holds the
    stored lanes (None when every diagonal is generated)."""
    acc = acc_dtype(store_dtype, x.dtype)
    x_pad = jnp.pad(x, (pad0, pad1)).astype(acc)
    y = jnp.zeros(n, dtype=acc)
    ks = 0
    for off, spec in diags:
        xs = jax.lax.dynamic_slice(x_pad, (pad0 + off,), (n,))
        if spec is None:
            y = y + jnp.asarray(data)[ks].astype(acc) * xs
            ks += 1
            continue
        p, lo, hi, gvr = spec
        contrib = gvr * xs
        if p:
            mask = _rule_mask(p, lo, hi, np.dtype(acc))
            contrib = (contrib.reshape(n // p, p) * mask[None, :]).reshape(n)
        y = y + contrib
    return y


def mf_spmm_xla(data, X: jnp.ndarray, *, diags, n, pad0, pad1,
                store_dtype) -> jnp.ndarray:
    """Multi-vector analogue: 2-D shifted slices, masks broadcast over
    columns of the block vector."""
    acc = acc_dtype(store_dtype, X.dtype)
    X_pad = jnp.pad(X, ((pad0, pad1), (0, 0))).astype(acc)
    b = X.shape[1]
    Y = jnp.zeros((n, b), dtype=acc)
    ks = 0
    for off, spec in diags:
        Xs = jax.lax.dynamic_slice(X_pad, (pad0 + off, 0), (n, b))
        if spec is None:
            Y = Y + jnp.asarray(data)[ks].astype(acc)[:, None] * Xs
            ks += 1
            continue
        p, lo, hi, gvr = spec
        contrib = gvr * Xs
        if p:
            mask = _rule_mask(p, lo, hi, np.dtype(acc))
            contrib = (contrib.reshape(n // p, p, b)
                       * mask[None, :, None]).reshape(n, b)
        Y = Y + contrib
    return Y


def mf_spmv(op: MatrixFreeOperator, x: jnp.ndarray) -> jnp.ndarray:
    return mf_spmv_xla(op.data, x, **_xla_static(op))


def mf_spmm(op: MatrixFreeOperator, X: jnp.ndarray) -> jnp.ndarray:
    return mf_spmm_xla(op.data, X, **_xla_static(op))


# ---------------------------------------------------------------------------
# loop reference: one boundary-clipped segment per diagonal, host masks
# ---------------------------------------------------------------------------


def mf_spmv_loop(op: MatrixFreeOperator, x: jnp.ndarray) -> jnp.ndarray:
    """Paper-fidelity oracle: per-diagonal boundary-clipped slice adds with
    host-computed (static) validity masks.  Slow, obviously correct."""
    n, ncols = op.shape
    diags = mf_tables(op)
    acc = acc_dtype(_storage_dtype(op), x.dtype)
    y = jnp.zeros(n, dtype=acc)
    ks = 0
    for k, (off, spec) in enumerate(diags):
        lo_b, hi_b = max(0, -off), min(n, ncols - off)
        if hi_b <= lo_b:
            continue
        xs = jax.lax.dynamic_slice(x, (lo_b + off,), (hi_b - lo_b,)).astype(acc)
        if spec is None:
            contrib = jnp.asarray(op.data)[ks, lo_b:hi_b].astype(acc) * xs
            ks += 1
        else:
            p, lo, hi, gvr = spec
            contrib = gvr * xs
            if p:
                i = np.arange(lo_b, hi_b)
                mask = (i % p >= lo) & (i % p < hi)
                contrib = jnp.where(jnp.asarray(mask), contrib, 0)
        y = y.at[lo_b:hi_b].add(contrib)
    return y


# ---------------------------------------------------------------------------
# Pallas: tiled rows, generated diagonals as iota compares in VMEM
# ---------------------------------------------------------------------------


def _mf_kernel(*refs, diags, t8, pad0, n_stored):
    if n_stored:
        data_ref, x_ref, o_ref = refs
    else:
        x_ref, o_ref = refs
    i = pl.program_id(0)
    shape = (t8, KD.LANES)
    row = (i * (t8 * KD.LANES)
           + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * KD.LANES
           + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    acc = jnp.zeros(shape, dtype=o_ref.dtype)
    ks = 0
    for off, spec in diags:  # static unroll over the diagonal set
        xs = KD.shifted_window(x_ref, i, pad0 + off, t8)
        if spec is None:
            contrib = data_ref[ks].astype(o_ref.dtype) * xs
            ks += 1
        else:
            p, lo, hi, gvr = spec
            contrib = gvr * xs
            if p:
                r = row % p
                contrib = jnp.where((r >= lo) & (r < hi), contrib, 0)
        acc = acc + contrib
    o_ref[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=("diags", "n_pad", "tile", "pad0", "interpret",
                     "vmem_limit"),
)
def mf_spmv_arrays(
    data,                # (n_stored, n_pad // 128, 128) or None when all generated
    x2: jnp.ndarray,     # (rows, 128) accumulation dtype, from dia_spmv.pad_x
    *,
    diags: tuple,
    n_pad: int,
    tile: int,
    pad0: int,
    interpret: bool,
    vmem_limit: int | None = None,
) -> jnp.ndarray:
    """One SpMV -> ``(n_pad // 128, 128)``."""
    n_stored = 0 if data is None else data.shape[0]
    t8 = KD.check_tile(tile) // KD.LANES
    r_pad = n_pad // KD.LANES
    assert r_pad % t8 == 0
    kernel = functools.partial(_mf_kernel, diags=diags, t8=t8, pad0=pad0,
                               n_stored=n_stored)
    in_specs = [pl.BlockSpec(x2.shape, lambda i: (0, 0))]
    operands = [x2]
    if n_stored:
        in_specs.insert(0, pl.BlockSpec((n_stored, t8, KD.LANES),
                                        lambda i: (0, i, 0)))
        operands.insert(0, data)
    return pl.pallas_call(
        kernel,
        grid=(r_pad // t8,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((t8, KD.LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, KD.LANES), x2.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(*operands)


def mf_prepare(op: MatrixFreeOperator, tile: int = KD.TILE_QUANTUM):
    """Host-side Pallas padding: stored lanes padded to a tile multiple and
    laid out lane-dense, x rows covering every shifted window of the grid."""

    def build():
        n, _ = op.shape
        n_pad = -(-n // KD.check_tile(tile)) * tile
        pad0 = max(0, -min(op.offsets))
        rows = KD.x_rows(pad0, n_pad, max(op.offsets))
        data = None
        if op.n_stored:
            data = np.zeros((op.n_stored, n_pad), dtype=_storage_dtype(op))
            data[:, :n] = np.asarray(op.data)
            data = data.reshape(op.n_stored, -1, KD.LANES)
        return data, pad0, rows, mf_tables(op), n, n_pad

    return cached(op, f"_mf_prepared_{tile}", "mf_pallas_prepare", build)


def _pallas_claim(m: MatrixFreeOperator, tile: int) -> int:
    n_pad = -(-m.shape[0] // tile) * tile
    rows = KD.x_rows(max(0, -min(m.offsets)), n_pad, max(m.offsets))
    return KD.vmem_bytes(m.n_stored, tile, rows, _storage_dtype(m).itemsize)


def matrix_free_autotune(m: MatrixFreeOperator, ctx: KernelContext) -> int:
    """Row-tile pick for the Pallas kernel: the largest tile whose stored
    slab + resident x claim fits the VMEM budget and whose padding waste
    stays under one tile of useful rows."""
    n = m.shape[0]
    for tile in (4 * KD.TILE_QUANTUM, 2 * KD.TILE_QUANTUM):
        if tile <= n and hw.vmem_fits(_pallas_claim(m, tile), ctx.chip):
            return tile
    return KD.TILE_QUANTUM


# --- registry entries -------------------------------------------------------


@register_kernel("matrix_free", "spmv", "xla",
                 description="generated diagonals: shifted reads + iota masks")
def _build_spmv(op: MatrixFreeOperator, ctx) -> CompiledKernel:
    return CompiledKernel(
        lambda data, x: mf_spmv_xla(data, x, **_xla_static(op)), "xla",
        operands=to_device(op, op.data)[0])


@register_kernel("matrix_free", "spmm", "xla",
                 description="multi-vector generated-diagonal shifted reads")
def _build_spmm(op: MatrixFreeOperator, ctx) -> CompiledKernel:
    return CompiledKernel(
        lambda data, X: mf_spmm_xla(data, X, **_xla_static(op)), "xla",
        operands=to_device(op, op.data)[0])


@register_kernel("matrix_free", "spmv", "loop_reference", auto=False,
                 description="per-diagonal clipped-segment oracle, host masks")
def _build_spmv_loop(op: MatrixFreeOperator, ctx) -> CompiledKernel:
    return closure_kernel(lambda x: mf_spmv_loop(op, x), "loop")


@register_kernel("matrix_free", "spmm", "loop_reference", auto=False,
                 description="column-by-column per-diagonal oracles")
def _build_spmm_loop(op: MatrixFreeOperator, ctx) -> CompiledKernel:
    return closure_kernel(spmm_by_columns(lambda x: mf_spmv_loop(op, x)),
                          "loop")


def _probe_mf_pallas(m, ctx: KernelContext) -> Capability:
    cap = _probe_pallas_dtype(m, ctx)
    if not cap.ok or m is None:
        return cap
    if m.n_diags == 0:
        return Capability(False, "no diagonals (empty descriptor)")
    tile = ctx.tile or matrix_free_autotune(m, ctx)
    if tile % KD.TILE_QUANTUM:
        return Capability(False, f"tile {tile} is not a multiple of "
                                 f"{KD.TILE_QUANTUM}")
    if not hw.vmem_fits(_pallas_claim(m, tile), ctx.chip):
        return Capability(False, "stored lanes + padded x exceed the VMEM budget")
    return CAP_OK


def _build_mf_pallas(op: MatrixFreeOperator, ctx: KernelContext,
                     interpret: bool) -> CompiledKernel:
    tile = ctx.tile or matrix_free_autotune(op, ctx)
    data, pad0, rows, diags, n, n_pad = mf_prepare(op, tile)
    label = "pallas-interpret" if interpret else "pallas"
    odt = acc_dtype(_storage_dtype(op), np.float32)
    limit = hw.vmem_limit(_pallas_claim(op, tile))

    def kernel(dataj, x):
        y = mf_spmv_arrays(dataj, KD.pad_x(x, pad0, rows, odt), diags=diags,
                           n_pad=n_pad, tile=tile, pad0=pad0,
                           interpret=interpret, vmem_limit=limit)
        return y.reshape(-1)[:n]

    return CompiledKernel(kernel, label, tile, to_device(op, data)[0])


@register_kernel("matrix_free", "spmv", "pallas",
                 probe=_probe_mf_pallas, autotune=matrix_free_autotune,
                 description="tiled rows; cols = row + offset in-registers")
def _build_mf_pallas_compiled(op: MatrixFreeOperator, ctx) -> CompiledKernel:
    return _build_mf_pallas(op, ctx, interpret=False)


@register_kernel("matrix_free", "spmv", "pallas_interpret",
                 probe=_probe_mf_pallas, autotune=matrix_free_autotune,
                 description="the same tiled kernel via the interpreter")
def _build_mf_pallas_interpret(op: MatrixFreeOperator, ctx) -> CompiledKernel:
    return _build_mf_pallas(op, ctx, interpret=True)


def _build_mf_pallas_spmm(op, ctx, interpret: bool) -> CompiledKernel:
    ck = _build_mf_pallas(op, ctx, interpret)
    return CompiledKernel(spmm_by_columns(ck.kernel), ck.label, ck.choice,
                          ck.operands)


@register_kernel("matrix_free", "spmm", "pallas",
                 probe=_probe_mf_pallas, autotune=matrix_free_autotune,
                 description="column-by-column over the tiled spmv kernel")
def _build_mf_pallas_spmm_compiled(op: MatrixFreeOperator, ctx) -> CompiledKernel:
    return _build_mf_pallas_spmm(op, ctx, interpret=False)


@register_kernel("matrix_free", "spmm", "pallas_interpret",
                 probe=_probe_mf_pallas, autotune=matrix_free_autotune,
                 description="column-by-column over the interpreted kernel")
def _build_mf_pallas_spmm_interpret(op: MatrixFreeOperator, ctx) -> CompiledKernel:
    return _build_mf_pallas_spmm(op, ctx, interpret=True)
