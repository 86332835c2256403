"""CSR row-split SpMV Pallas kernel — the cache-based CRS loop, TPU-tiled.

Paper mapping: the CRS kernel's outer loop over rows with a register-held
accumulator becomes a grid over *row tiles* of R rows.  Each tile's ragged
nnz segment ``[row_ptr[t*R], row_ptr[(t+1)*R))`` is padded host-side to the
global max tile width E (one (T, E) slab each for values, column ids and
tile-local row ids), so every grid step streams one uniform (TB, E) slab —
the row-split analogue of the SELL kernel's chunk slabs, but in *original
row order* (no sigma sort, no perm scatter on the way out).

The per-tile reduction is a one-hot contraction: ``out[t, r] = sum_e
val[t, e] * x[col[t, e]] * (rid[t, e] == r)`` — an (R, E) mask matmul per
tile, which is exactly the MXU-friendly way to express a tiny segment-sum
inside a kernel (padding slots carry ``rid == R`` and fall off the one-hot).

x is held fully VMEM-resident, as in the SELL kernel (the paper's "input
vector in cache" regime by construction).

This kernel does not lower for TPU: Mosaic refuses the 1-D ``jnp.take`` of
x in VMEM ("Only 2D gather is supported"), so the registry's probe rejects
the compiled entry there (``registry.GATHER_UNSUPPORTED``) and the kernel runs
through the interpreter only, as a parity-tested formulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import CSR
from .cache import cached, register_stat
from .accum import acc_dtype

register_stat("csr_rowsplit_slabs")


def _csr_rowsplit_kernel(col_ref, val_ref, rid_ref, x_ref, o_ref, *, R):
    idx = col_ref[...]                    # (TB, E) int32
    vals = val_ref[...]                   # (TB, E)
    rid = rid_ref[...]                    # (TB, E) int32, padding -> R
    x = x_ref[...]                        # (N,)
    g = jnp.take(x, idx.reshape(-1), axis=0).reshape(idx.shape)
    prod = vals.astype(o_ref.dtype) * g.astype(o_ref.dtype)      # (TB, E)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, 1, R), 2)    # (1, 1, R)
    onehot = (rid[..., None] == lanes).astype(o_ref.dtype)       # (TB, E, R)
    o_ref[...] = jnp.einsum("te,ter->tr", prod, onehot)


@functools.partial(
    jax.jit,
    static_argnames=("R", "tile_block", "interpret", "out_dtype", "vmem_limit"),
)
def csr_rowsplit_arrays(
    col2: jnp.ndarray,   # (T, E) int32
    val2: jnp.ndarray,   # (T, E)
    rid2: jnp.ndarray,   # (T, E) int32 tile-local row ids, padding -> R
    x: jnp.ndarray,      # (N,)
    *,
    R: int = 8,
    tile_block: int = 8,
    interpret: bool,
    out_dtype=None,
    vmem_limit: int | None = None,
) -> jnp.ndarray:
    """Row-split CSR slabs -> (T, R) row-tile results (original row order).

    T must be divisible by ``tile_block`` (pad at prepare time).
    """
    T, E = col2.shape
    assert T % tile_block == 0, (T, tile_block)
    odt = out_dtype or acc_dtype(val2.dtype, x.dtype)
    kernel = functools.partial(_csr_rowsplit_kernel, R=R)
    return pl.pallas_call(
        kernel,
        grid=(T // tile_block,),
        in_specs=[
            pl.BlockSpec((tile_block, E), lambda i: (i, 0)),
            pl.BlockSpec((tile_block, E), lambda i: (i, 0)),
            pl.BlockSpec((tile_block, E), lambda i: (i, 0)),
            pl.BlockSpec((x.shape[0],), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((tile_block, R), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, R), odt),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(col2, val2, rid2, x)


def csr_rowsplit_geometry(m: CSR, R: int = 8, pad_to: int = 8,
                          tile_block: int = 8) -> tuple[int, int]:
    """(T, E) slab geometry in O(n) host work — no slab materialization.

    Probes and the autotune hook need only the geometry for the VMEM
    claim; building the actual (T, E) slabs is deferred to
    ``csr_rowsplit_prepare`` (i.e. to an entry that actually compiles).
    """
    rp = np.asarray(m.row_ptr, dtype=np.int64)
    n = m.n_rows
    T = -(-max(1, -(-n // R)) // tile_block) * tile_block
    bounds = rp[np.minimum(np.arange(T + 1) * R, n)]
    max_tile = int(np.diff(bounds).max()) if T else 0
    E = max(pad_to, -(-max(1, max_tile) // pad_to) * pad_to)
    return T, E


def csr_rowsplit_prepare(m: CSR, R: int = 8, pad_to: int = 8,
                         tile_block: int = 8):
    """Host-side slab build, cached once per (container, geometry).

    Returns ``(col2, val2, rid2, T, E)`` numpy slabs of shape (T, E): T row
    tiles of R rows, each padded to the global max tile nnz E (rounded up
    to ``pad_to``); T itself is padded to a ``tile_block`` multiple.  The
    streamed-bytes cost of this padding is what the perfmodel's row-split
    accounting charges (a tile-granular ELL, in row order).
    """

    def build():
        rp = np.asarray(m.row_ptr, dtype=np.int64)
        ci = np.asarray(m.col_idx)
        v = np.asarray(m.val)
        n = m.n_rows
        T, E = csr_rowsplit_geometry(m, R=R, pad_to=pad_to,
                                     tile_block=tile_block)
        col2 = np.zeros((T, E), dtype=np.int32)
        val2 = np.zeros((T, E), dtype=v.dtype)
        rid2 = np.full((T, E), R, dtype=np.int32)   # padding -> R (no row)
        for t in range(T):
            lo, hi = int(rp[min(t * R, n)]), int(rp[min((t + 1) * R, n)])
            L = hi - lo
            if L == 0:
                continue
            col2[t, :L] = ci[lo:hi]
            val2[t, :L] = v[lo:hi]
            # tile-local row id per element
            local_ptr = rp[min(t * R, n): min((t + 1) * R, n) + 1] - lo
            lens = np.diff(local_ptr)
            rid2[t, :L] = np.repeat(np.arange(len(lens), dtype=np.int32), lens)
        return col2, val2, rid2, T, E

    return cached(m, f"_rowsplit_{R}_{pad_to}_{tile_block}",
                  "csr_rowsplit_slabs", build)


def rowsplit_vmem_bytes(tile_block: int, E: int, R: int, n: int,
                        val_bytes: int = 4, idx_bytes: int = 4,
                        x_bytes: int = 4) -> int:
    """Working-set claim of one grid step (double-buffered slabs + x)."""
    slab = tile_block * E
    return slab * (val_bytes + 2 * idx_bytes) * 2 + n * x_bytes \
        + tile_block * R * 4
