"""SELL-C-sigma SpMV Pallas kernel — the TPU-native blocked-JDS kernel.

Paper mapping: NBJDS's "only the elements of the current block are processed
for all jagged diagonals that have entries in this block, to the effect that
the corresponding part of the result vector remains in cache" becomes: the
(CB, C) result tile lives in VMEM/VREGs for the whole sweep over the chunk's
jagged diagonals (the W axis).  RBJDS's contiguous block storage is the
(nc, W, C) slab layout itself; SOJDS's stride sorting happened at format-
construction time (``SELL.from_csr(sort_cols=True)``).

TPU tiling:
  * C (chunk height) should be a multiple of the 128-lane dimension for VPU
    efficiency (C=128 default; C=8 supported for small problems).
  * The x vector is held fully VMEM-resident (one (N,) block): SpMV input
    vectors up to ~30M fp32 fit v5e's 128 MiB VMEM — this *is* the paper's
    "input vector in cache" regime, achieved by construction instead of by
    hoping the cache keeps it.
  * val/col slabs stream through VMEM tiles of (CB, WB, C) via the grid
    pipeline (the analogue of the paper's hardware prefetcher, but explicit
    and guaranteed — see docs/DESIGN.md on prefetch adaptation).

Grid: (nc/CB, W/WB); the W axis accumulates into the same output block
(revisited output => sequential W iterations, init at w==0).

Neither kernel lowers for TPU: the in-kernel ``jnp.take`` of x is a 1-D
gather from VMEM, which Mosaic refuses (``registry.GATHER_UNSUPPORTED``).  The
registry's probes reject the compiled entries there; the interpreter runs
them as a parity-tested formulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .accum import acc_dtype


def _sell_kernel(col_ref, val_ref, x_ref, o_ref):
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    idx = col_ref[...]  # (CB, WB, C) int32
    vals = val_ref[...]  # (CB, WB, C)
    x = x_ref[...]  # (N,)
    g = jnp.take(x, idx.reshape(-1), axis=0).reshape(idx.shape)
    o_ref[...] += jnp.sum(vals.astype(o_ref.dtype) * g.astype(o_ref.dtype), axis=1)


@functools.partial(
    jax.jit, static_argnames=("chunk_block", "width_block", "interpret",
                              "out_dtype", "vmem_limit")
)
def sell_spmv_arrays(
    col3: jnp.ndarray,
    val3: jnp.ndarray,
    x: jnp.ndarray,
    *,
    chunk_block: int = 8,
    width_block: int | None = None,
    interpret: bool,
    out_dtype=None,
    vmem_limit: int | None = None,
) -> jnp.ndarray:
    """col3/val3: (nc, W, C); x: (N,) -> (nc, C) tile results.

    nc must be divisible by chunk_block and W by width_block (pad at format
    construction; ``SELL.padded_views(pad_width_to=...)``).
    """
    nc, W, C = col3.shape
    wb = width_block or W
    assert nc % chunk_block == 0, (nc, chunk_block)
    assert W % wb == 0, (W, wb)
    odt = out_dtype or acc_dtype(val3.dtype, x.dtype)
    grid = (nc // chunk_block, W // wb)
    return pl.pallas_call(
        _sell_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk_block, wb, C), lambda i, w: (i, w, 0)),
            pl.BlockSpec((chunk_block, wb, C), lambda i, w: (i, w, 0)),
            pl.BlockSpec((x.shape[0],), lambda i, w: (0,)),
        ],
        out_specs=pl.BlockSpec((chunk_block, C), lambda i, w: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nc, C), odt),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(col3, val3, x)


def sell_spmv_scatter(tiles: jnp.ndarray, perm, n_rows: int) -> jnp.ndarray:
    """Un-permute (nc, C) tiles back to original row order.  ``perm`` is
    the *inverse* row permutation applied as a gather (the sort perm is a
    bijection, so no scatter-add is ever needed); ``None`` = natural
    order (reshape + slice)."""
    flat = tiles.reshape(-1)
    return flat[:n_rows] if perm is None else flat[perm]


def _sell_mm_kernel(col_ref, val_ref, x_ref, o_ref):
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    idx = col_ref[...]   # (CB, WB, C) int32
    vals = val_ref[...]  # (CB, WB, C)
    X = x_ref[...]       # (N, K)
    g = jnp.take(X, idx.reshape(-1), axis=0).reshape(idx.shape + (X.shape[1],))
    o_ref[...] += jnp.einsum("bwc,bwck->bck", vals.astype(o_ref.dtype),
                             g.astype(o_ref.dtype))


@functools.partial(
    jax.jit, static_argnames=("chunk_block", "width_block", "interpret",
                              "out_dtype", "vmem_limit")
)
def sell_spmm_arrays(
    col3: jnp.ndarray,
    val3: jnp.ndarray,
    X: jnp.ndarray,
    *,
    chunk_block: int = 8,
    width_block: int | None = None,
    interpret: bool,
    out_dtype=None,
    vmem_limit: int | None = None,
) -> jnp.ndarray:
    """Multi-vector SELL kernel: col3/val3 (nc, W, C); X (N, K) -> (nc, C, K).

    The matrix slabs stream exactly as in ``sell_spmv_arrays`` while X stays
    VMEM-resident whole — one matrix pass for all K right-hand sides (the
    serving layer's batching lever).  The block choice is shared with the
    SpMV kernel; the VMEM claim grows by the (N + CB*C) * K term, so very
    wide batches on very large x may need a smaller chunk_block.
    """
    nc, W, C = col3.shape
    wb = width_block or W
    assert nc % chunk_block == 0, (nc, chunk_block)
    assert W % wb == 0, (W, wb)
    K = X.shape[1]
    odt = out_dtype or acc_dtype(val3.dtype, X.dtype)
    grid = (nc // chunk_block, W // wb)
    return pl.pallas_call(
        _sell_mm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk_block, wb, C), lambda i, w: (i, w, 0)),
            pl.BlockSpec((chunk_block, wb, C), lambda i, w: (i, w, 0)),
            pl.BlockSpec((X.shape[0], K), lambda i, w: (0, 0)),
        ],
        out_specs=pl.BlockSpec((chunk_block, C, K), lambda i, w: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nc, C, K), odt),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(col3, val3, X)


def sell_spmm_scatter(tiles: jnp.ndarray, perm, n_rows: int) -> jnp.ndarray:
    """Un-permute (nc, C, K) tiles back to original row order (inverse-perm
    gather; ``None`` = natural order — see ``sell_spmv_scatter``)."""
    K = tiles.shape[-1]
    flat = tiles.reshape(-1, K)
    return flat[:n_rows] if perm is None else flat[perm]


def vmem_bytes(chunk_block: int, width_block: int, C: int, n: int,
               val_bytes: int = 4, idx_bytes: int = 4, x_bytes: int = 4,
               k: int = 1) -> int:
    """Working-set claim for the BlockSpec choice (must be << VMEM).

    ``k`` is the SpMM batch width (1 = SpMV): x and the output tile scale
    by it, the matrix slabs do not.
    """
    slab = chunk_block * width_block * C
    return slab * (val_bytes + idx_bytes) * 2 + n * x_bytes * k \
        + chunk_block * C * 4 * k
