"""DIA (dense diagonal) SpMV Pallas kernel — the zero-index-traffic format.

For the Holstein-Hubbard matrix ~60 % of non-zeros sit in 12 dense secondary
diagonals (paper Fig. 5).  Stored as DIA, each of those elements costs one
val stream + one *stride-1 shifted* x read — no column indices at all.  The
balance drops from CRS's 10 B/F to ~6 B/F (fp64), and on TPU the shifted
reads are plain vector loads, no gather unit involved.

Layout (lane-dense, what Mosaic lowers): vectors live as ``(rows, 128)``
arrays — element ``e`` at ``[e // 128, e % 128]``.  The grid runs over
output tiles of ``tile`` rows (``tile // 128`` sublane rows); x is
VMEM-resident, zero-padded by ``pad0`` on the left and far enough on the
right that every shifted window ``[base + pad0 + off, +tile)`` is in range
for all static ``offsets``.  A window is read by :func:`shifted_window`:
one aligned-base ref slice, a static lane rotate and a select — never a
``dynamic_slice`` of a value, which Mosaic does not lower.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import DIA
from .accum import acc_dtype

LANES = 128
#: row tiles are multiples of this: 32 sublane rows of 128 lanes, the
#: native tile of the narrowest (8-bit) stored dtype
TILE_QUANTUM = 32 * LANES


def check_tile(tile: int) -> int:
    if tile <= 0 or tile % TILE_QUANTUM:
        raise ValueError(f"tile={tile} must be a positive multiple of "
                         f"{TILE_QUANTUM}")
    return tile


def shifted_window(x_ref, i, c: int, t8: int):
    """Rows ``[i*t8*128 + c, +t8*128)`` of the flat vector held by the
    ``(rows, 128)`` ref, as a ``(t8, 128)`` value (``c`` static)."""
    q, r = divmod(c, LANES)
    w = x_ref[pl.ds(pl.multiple_of(i * t8, 8) + q, t8 + 8), :]
    if r == 0:
        return w[:t8]
    w = pltpu.roll(w, LANES - r, 1)          # w[a, l] <- w[a, (l + r) % 128]
    lane = jax.lax.broadcasted_iota(jnp.int32, (t8, LANES), 1)
    return jnp.where(lane < LANES - r, w[:t8], w[1:t8 + 1])


def x_rows(pad0: int, n_pad: int, max_off: int) -> int:
    """Sublane rows of the padded x: every window of every tile in range."""
    return -(-(pad0 + n_pad + max(0, max_off)) // LANES) + 8


def pad_x(x: jnp.ndarray, pad0: int, rows: int, dtype) -> jnp.ndarray:
    """``x`` zero-padded to ``pad0`` on the left and ``rows * 128`` in all,
    as the ``(rows, 128)`` operand of the shifted-window kernels."""
    x = x[: rows * LANES - pad0].astype(dtype)
    return jnp.pad(x, (pad0, rows * LANES - pad0 - x.shape[0])).reshape(rows, LANES)


def vmem_bytes(n_lanes: int, tile: int, rows: int, value_bytes: int = 4) -> int:
    """Working-set claim: double-buffered stored-lane blocks and output
    tiles, and the resident f32 x (double-buffered by the pipeline)."""
    return 2 * n_lanes * tile * value_bytes + 2 * tile * 4 + 2 * rows * LANES * 4


def _dia_kernel(data_ref, x_ref, o_ref, *, offsets, t8, pad0, scales):
    i = pl.program_id(0)
    acc = jnp.zeros((t8, LANES), dtype=o_ref.dtype)
    for k, off in enumerate(offsets):  # static unroll over stored diagonals
        xs = shifted_window(x_ref, i, pad0 + off, t8)
        contrib = data_ref[k].astype(o_ref.dtype) * xs
        if scales is not None:  # static per-diagonal dequant scale
            contrib = contrib * scales[k]
        acc = acc + contrib
    o_ref[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=("offsets", "tile", "pad0", "interpret", "scales",
                     "vmem_limit"),
)
def dia_spmv_arrays(
    data: jnp.ndarray,   # (nd, n_pad // 128, 128) — rows padded to tile multiple
    x2: jnp.ndarray,     # (rows, 128) f32, from pad_x
    *,
    offsets: tuple[int, ...],
    tile: int,
    pad0: int,
    interpret: bool,
    scales: tuple[float, ...] | None = None,
    vmem_limit: int | None = None,
) -> jnp.ndarray:
    """One SpMV over the stored diagonals -> ``(n_pad // 128, 128)``."""
    nd, r_pad, _ = data.shape
    t8 = check_tile(tile) // LANES
    assert r_pad % t8 == 0
    kernel = functools.partial(_dia_kernel, offsets=offsets, t8=t8, pad0=pad0,
                               scales=scales)
    return pl.pallas_call(
        kernel,
        grid=(r_pad // t8,),
        in_specs=[
            pl.BlockSpec((nd, t8, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec(x2.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((t8, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, LANES), x2.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(data, x2)


def dia_prepare(m: DIA, tile: int = TILE_QUANTUM):
    """Host-side padding: returns (data (nd, n_pad//128, 128), pad0, rows,
    offsets, n) — ``rows`` is the padded x's sublane count."""
    offsets = tuple(int(o) for o in np.asarray(m.offsets))
    n = m.shape[0]
    n_pad = -(-n // check_tile(tile)) * tile
    data = np.zeros((max(1, len(offsets)), n_pad), dtype=np.asarray(m.data).dtype)
    if len(offsets):
        data[:, :n] = np.asarray(m.data)
    pad0 = max(0, -min(offsets)) if offsets else 0
    rows = x_rows(pad0, n_pad, max(offsets) if offsets else 0)
    return data.reshape(data.shape[0], -1, LANES), pad0, rows, offsets, n


def dia_spmv(m: DIA, x: jnp.ndarray, *, tile: int = TILE_QUANTUM,
             interpret: bool) -> jnp.ndarray:
    data, pad0, rows, offsets, n = dia_prepare(m, tile)
    if not offsets:
        return jnp.zeros(n, dtype=x.dtype)
    odt = acc_dtype(data.dtype, x.dtype)
    y = dia_spmv_arrays(jnp.asarray(data), pad_x(x, pad0, rows, odt),
                        offsets=offsets, tile=tile, pad0=pad0,
                        interpret=interpret)
    return y.reshape(-1)[:n]
