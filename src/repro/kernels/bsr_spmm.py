"""Block-sparse (BSR/BELL) matmul Pallas kernel — sparse weights on the MXU.

The paper's closing observation on "dense subblocks ... exploited to generate
a specialized format" is the 2009 ancestor of today's structured-sparse
weight inference.  On TPU the winning block shape is MXU-aligned
((bm, bk) multiples of (8, 128) for fp32, (16, 128) bf16): each stored block
feeds the systolic array as a dense subtile, index traffic amortizes over
bm*bk elements (balance ~(v + i/(bm*bk)) B/F -> essentially dense-GEMM
balance at any sparsity).

Layout: BELL (block-ELL) — fixed ``nbpp`` block slots per block-row, padded
with zero blocks.  The column ids live in SMEM via scalar prefetch, so the
X-block fetch address for grid step (i, j) is known *before* the step runs
and the HBM->VMEM stream is fully pipelined (the "prefetcher" is explicit).

Grid: (nbr, nbpp) — output block revisited along j, accumulated in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import BSR
from ..utils import hw
from .accum import acc_dtype


def _bell_kernel(bc_ref, blk_ref, x_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = blk_ref[0, 0]  # (bm, bk)
    o_ref[...] += jnp.dot(a, x_ref[...], preferred_element_type=o_ref.dtype)


#: SMEM left to the kernel's own scalars beside the prefetched table
SMEM_RESERVE_BYTES = 32 * 1024


def bell_table_smem_bytes(nbr: int, nbpp: int) -> int:
    """SMEM the scalar-prefetched (nbr, nbpp) int32 column table takes:
    Mosaic pads its minor dimension to 128 words."""
    return 4 * nbr * (-(-nbpp // 128) * 128)


def bell_vmem_bytes(bm: int, bk: int, n: int, value_bytes: int = 4,
                    x_bytes: int = 4) -> int:
    """Working-set claim: double-buffered block, X panel and output tile."""
    return 2 * (bm * bk * value_bytes + bk * n * x_bytes + bm * n * 4)


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def bell_spmm_arrays(
    bcols: jnp.ndarray,   # (nbr, nbpp) int32
    blocks: jnp.ndarray,  # (nbr, nbpp, bm, bk)
    X: jnp.ndarray,       # (K, N)
    *,
    interpret: bool,
    out_dtype=None,
) -> jnp.ndarray:
    nbr, nbpp, bm, bk = blocks.shape
    K, N = X.shape
    assert K % bk == 0
    odt = out_dtype or acc_dtype(blocks.dtype, X.dtype)
    claim = bell_vmem_bytes(bm, bk, N, blocks.dtype.itemsize, X.dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nbr, nbpp),
        in_specs=[
            pl.BlockSpec((1, 1, bm, bk), lambda i, j, bc: (i, j, 0, 0)),
            pl.BlockSpec((bk, N), lambda i, j, bc: (bc[i, j], 0)),
        ],
        out_specs=pl.BlockSpec((bm, N), lambda i, j, bc: (i, 0)),
    )
    return pl.pallas_call(
        _bell_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nbr * bm, N), odt),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=hw.vmem_limit(claim)),
        interpret=interpret,
    )(bcols, blocks, X)


# ---------------------------------------------------------------------------
# BSR -> BELL host-side conversion
# ---------------------------------------------------------------------------


def bsr_to_bell(m: BSR) -> tuple[np.ndarray, np.ndarray]:
    """Pad each block-row to the max blocks-per-row; zero blocks are inert."""
    bm, bk = m.block_shape
    brp = np.asarray(m.block_row_ptr)
    bci = np.asarray(m.block_col_idx)
    blocks = np.asarray(m.blocks)
    nbr = len(brp) - 1
    lens = brp[1:] - brp[:-1]
    nbpp = int(max(1, lens.max())) if nbr else 1
    bcols = np.zeros((nbr, nbpp), dtype=np.int32)
    slab = np.zeros((nbr, nbpp, bm, bk), dtype=blocks.dtype)
    for r in range(nbr):
        L = int(lens[r])
        bcols[r, :L] = bci[brp[r] : brp[r] + L]
        slab[r, :L] = blocks[brp[r] : brp[r] + L]
    return bcols, slab


def bell_fill_ratio(m: BSR) -> float:
    """Streamed blocks (incl. padding) / stored blocks."""
    brp = np.asarray(m.block_row_ptr)
    lens = brp[1:] - brp[:-1]
    nbpp = int(max(1, lens.max())) if len(lens) else 1
    return nbpp * len(lens) / max(1, int(lens.sum()))
