"""SELL-C-sigma kernels (blocked JDS: NBJDS/RBJDS/SOJDS unified).

Registry entries: ``(sell, {spmv, spmm}, {xla, loop_reference, pallas,
pallas_interpret})``.  The Pallas entries wrap the TPU kernels in
``sell_spmv.py``; their shared :func:`sell_autotune` hook owns the
``(chunk_block, width_block)`` selection (model-driven via
``perfmodel.select_pallas_blocks``), the override re-claim and the
grid-divisibility adjustment that used to live inline in ``core.plan`` —
the plan layer and any other consumer now get one implementation.

Stream-byte note (see ``perfmodel.balance_of(backend=...)``): the XLA
entry carries *two* formulations and picks per container
(``perfmodel.sell_xla_uses_flat``).  The padded form consumes the globally
padded (nc, W_max, C) views — ``nc * W_max * C`` elements per call,
regular einsum-friendly shapes, but blind to sigma-sorting.  The flat form
(``sell_spmv_flat``) streams the chunk-local layout directly —
``sum_c w_c * C`` elements plus one row id each, a gather + segment-sum
exactly like the distributed slab kernel — so sigma-sorted packs of
irregular matrices actually move fewer bytes under XLA too.  The Pallas
kernels and the loop oracle stream flat without the row-id side stream.
The perfmodel accounts for all three regimes per backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.formats import SELL
from ..utils import hw
from . import sell_spmv as KP
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
from . import registry

register_stat("sell_padded_views")
register_stat("sell_flat_rids")


def sell_padded_views(m: SELL, pad_width_to: int = 1):
    """Fully padded (nc, W, C) numpy views + per-chunk widths, built once and
    cached per ``pad_width_to`` (the Pallas width-block granularity)."""

    return cached(m, f"_padded_views_{pad_width_to}", "sell_padded_views",
                  lambda: m.padded_views(pad_width_to=pad_width_to))


def sell_flat_rids(m: SELL):
    """Per-element chunk-row segment ids of the flat chunk-column-major
    layout, built once and cached on the container.

    Element ``p`` of chunk ``c`` (a column-major ``(w, C)`` slab) belongs
    to in-chunk row ``p % C``, so its segment is ``c*C + p % C`` — the
    index the flat segment-sum formulation reduces on.
    """

    def build():
        cp = np.asarray(m.chunk_ptr)
        cw = np.asarray(m.chunk_width)
        C = m.C
        rid = np.empty(int(cp[-1]), dtype=np.int32)
        lane = np.arange(C, dtype=np.int32)
        for c in range(m.n_chunks):
            w = int(cw[c])
            rid[cp[c]:cp[c + 1]] = c * C + np.tile(lane, w)
        return rid

    return cached(m, "_flat_rids", "sell_flat_rids", build)


def sell_perm_is_natural(m: SELL) -> bool:
    """True when the pack's row permutation is the identity (pad rows
    excluded) — every regular matrix sigma-sorts to this, and sigma=1
    always does.  The kernels then skip the perm-scatter entirely
    (XLA:CPU scatter-add is serial and an order of magnitude slower than
    the reshape+slice it replaces)."""
    memo = getattr(m, "_perm_natural", None)
    if memo is None:
        p = np.asarray(m.perm)
        n = m.shape[0]
        memo = bool((p[:n] == np.arange(n, dtype=p.dtype)).all())
        object.__setattr__(m, "_perm_natural", memo)
    return memo


def _perm_arg(m: SELL):
    """Inverse-permutation operand for the kernels (host numpy), or None
    for the natural order.  ``inv[orig_row] = tile position of orig_row``:
    the sigma-sort perm is a bijection on real rows, so undoing it is a
    single n-element *gather* — never the scatter-add an ``.at[perm].add``
    would lower to (serial on XLA:CPU)."""
    if sell_perm_is_natural(m):
        return None
    inv = getattr(m, "_perm_inv", None)
    if inv is None:
        p = np.asarray(m.perm)
        n = m.shape[0]
        inv = np.empty(n, dtype=np.int32)
        pos = np.nonzero(p < n)[0]
        inv[p[pos]] = pos
        object.__setattr__(m, "_perm_inv", inv)
    return inv


def sell_spmv_padded(col3: jnp.ndarray, val3: jnp.ndarray, perm,
                     x: jnp.ndarray, n_rows: int, scale=None) -> jnp.ndarray:
    """Vectorised SELL on the fully padded (n_chunks, W, C) views.

    This is the shape the Pallas kernel consumes; also a fast XLA fallback.
    Reduces in ``acc_dtype`` (>= f32); ``scale`` is the optional per-chunk
    fp32 scale of a quantized container, applied to the reduced (nc, C)
    tiles before the un-permute.  ``perm`` is the *inverse* row
    permutation (``_perm_arg``) applied as a gather; ``None`` means the
    natural row order (reshape + slice, no indexing at all).
    """
    acc = acc_dtype(val3.dtype, x.dtype)
    gathered = jnp.take(x, col3, axis=0)  # (nc, W, C)
    tiles = jnp.sum(val3.astype(acc) * gathered.astype(acc), axis=1)  # (nc, C)
    if scale is not None:
        tiles = tiles * scale.astype(acc)[:, None]
    flat = tiles.reshape(-1)
    return flat[:n_rows] if perm is None else flat[perm]


def _padded_operands(m: SELL, pad_width_to: int = 1) -> tuple:
    """(col3, val3, inverse perm, scale) of the padded (nc, W, C) views."""
    col3, val3, _ = sell_padded_views(m, pad_width_to)
    return col3, val3, _perm_arg(m), m.scale


def _padded_spmv_kernel(ops, x, n_rows: int):
    col3, val3, perm, scale = ops
    return sell_spmv_padded(col3, val3, perm, x, n_rows, scale)


def _padded_spmm_kernel(ops, X, n_rows: int):
    col3, val3, perm, scale = ops
    return sell_spmm_padded(col3, val3, perm, X, n_rows, scale)


def sell_spmv(m: SELL, x: jnp.ndarray) -> jnp.ndarray:
    """Vectorized SELL via the cached padded 3-D views: one gather + one
    reduction over W + one perm-scatter (no host loop over chunks)."""
    return _padded_spmv_kernel(_padded_operands(m), x, m.shape[0])


def sell_spmm_padded(col3: jnp.ndarray, val3: jnp.ndarray, perm,
                     X: jnp.ndarray, n_rows: int, scale=None) -> jnp.ndarray:
    """Multi-vector SELL on the padded (nc, W, C) views (any padding works:
    extra zero columns contribute nothing).  ``perm`` = inverse-perm
    gather indices, ``None`` = natural row order."""
    acc = acc_dtype(val3.dtype, X.dtype)
    gathered = jnp.take(X, col3, axis=0)  # (nc, W, C, K)
    tiles = jnp.einsum("nwc,nwck->nck", val3.astype(acc),
                       gathered.astype(acc))  # (nc, C, K)
    if scale is not None:
        tiles = tiles * scale.astype(acc)[:, None, None]
    flat = tiles.reshape(-1, X.shape[1])
    return flat[:n_rows] if perm is None else flat[perm]


def sell_spmm(m: SELL, X: jnp.ndarray) -> jnp.ndarray:
    return _padded_spmm_kernel(_padded_operands(m), X, m.shape[0])


def sell_spmv_flat(col, val, rid, perm, x, n_rows: int, n_segments: int,
                   C: int, scale=None) -> jnp.ndarray:
    """Flat SELL: gather x by the chunk-column-major col stream, multiply,
    segment-sum on the per-element chunk-row ids, perm-scatter.

    Streams exactly ``sum_c w_c * C`` stored elements (plus one row id
    each) — the formulation that makes sigma-sorting pay under XLA.
    Padding elements carry ``col = 0, val = 0`` and contribute nothing;
    padding rows' segments are simply never gathered.  ``perm`` is the
    inverse row permutation (gather indices; ``None`` = natural order);
    ``scale`` is the per-chunk fp32 scale of a quantized container,
    repeated to the C rows of each chunk tile.
    """
    acc = acc_dtype(val.dtype, x.dtype)
    prod = val.astype(acc) * jnp.take(x, col, axis=0).astype(acc)
    tiles = jax.ops.segment_sum(prod, rid, num_segments=n_segments)
    if scale is not None:
        tiles = tiles * jnp.repeat(scale.astype(acc), C)
    return tiles[:n_rows] if perm is None else tiles[perm]


def sell_spmm_flat(col, val, rid, perm, X, n_rows: int, n_segments: int,
                   C: int, scale=None) -> jnp.ndarray:
    """Multi-vector flat SELL: one matrix pass for all K columns."""
    acc = acc_dtype(val.dtype, X.dtype)
    prod = val.astype(acc)[:, None] * jnp.take(X, col, axis=0).astype(acc)
    tiles = jax.ops.segment_sum(prod, rid, num_segments=n_segments)
    if scale is not None:
        tiles = tiles * jnp.repeat(scale.astype(acc), C)[:, None]
    return tiles[:n_rows] if perm is None else tiles[perm]


def _flat_operands(m: SELL) -> tuple:
    return m.col_idx, m.val, sell_flat_rids(m), _perm_arg(m), m.scale


def _flat_spmv_kernel(ops, x, n_rows: int, n_segments: int, C: int):
    col, val, rid, perm, scale = ops
    return sell_spmv_flat(col, val, rid, perm, x, n_rows, n_segments, C, scale)


def _flat_spmm_kernel(ops, X, n_rows: int, n_segments: int, C: int):
    col, val, rid, perm, scale = ops
    return sell_spmm_flat(col, val, rid, perm, X, n_rows, n_segments, C, scale)


def sell_spmv_loop(m: SELL, x: jnp.ndarray) -> jnp.ndarray:
    """Chunk-local jagged-diagonal traversal (host loop over chunks).

    Each chunk is a (width_c, C) column-major slab; the C-row result tile
    stays "in cache" (a register tile on TPU) for the whole chunk — exactly
    the paper's NBJDS blocking argument.  Kept as the paper-fidelity oracle;
    traces O(n_chunks) scatter-adds.
    """
    cp = np.asarray(m.chunk_ptr)
    cw = np.asarray(m.chunk_width)
    C = m.C
    n_rows = m.shape[0]
    acc = acc_dtype(jnp.asarray(m.val).dtype, x.dtype)
    val = jnp.asarray(m.val).astype(acc)
    ci = jnp.asarray(m.col_idx)
    perm = jnp.asarray(m.perm)
    scale = None if m.scale is None else np.asarray(m.scale)
    y = jnp.zeros(n_rows + 1, dtype=acc)
    for c in range(m.n_chunks):
        w = int(cw[c])
        lo, hi = int(cp[c]), int(cp[c + 1])
        slab_v = val[lo:hi].reshape(w, C)
        slab_x = jnp.take(x, ci[lo:hi], axis=0).reshape(w, C).astype(acc)
        tile = jnp.sum(slab_v * slab_x, axis=0)  # (C,)
        if scale is not None:
            tile = tile * float(scale[c])
        rows = perm[c * C : (c + 1) * C]  # original row ids; pad rows -> n_rows
        y = y.at[rows].add(tile)
    return y[:n_rows]


# --- autotune hooks (shared by plan + any other consumer) -------------------


def sell_sigma_autotune(row_lengths, C: int = 8, candidates=None):
    """Pack-time sigma selection: the registry-level entry point.

    sigma is fixed when the container is packed, so unlike the
    (chunk_block, width_block) hook below it runs on the *pattern* (row
    lengths), before conversion.  Returns ``(sigma, flat_pad_ratio)``;
    shared by ``perfmodel.select_format`` (cold picks), the ``--tune``
    measured tier (candidate enumeration) and ``corpus.corpus_stats``
    (occupancy-vs-sigma reporting).
    """
    from ..core import perfmodel as PM

    return PM.select_sell_sigma(row_lengths, C, candidates)


def sell_autotune(m: SELL, ctx: KernelContext):
    """Pick ``(chunk_block, width_block)`` for the Pallas SELL kernels.

    One implementation of the logic that used to be duplicated at the plan
    layer: the model-driven ``perfmodel.select_pallas_blocks`` choice,
    re-claimed VMEM when the caller overrides a block, and the
    grid-divisibility adjustment (``chunk_block`` must divide ``n_chunks``).
    Returns a ``perfmodel.BlockChoice``.
    """
    from ..core import perfmodel as PM

    cw = np.asarray(m.chunk_width)
    W0 = int(cw.max()) if cw.size else 1
    vb = int(np.dtype(np.asarray(m.val).dtype).itemsize)
    choice = PM.select_pallas_blocks(m.n_chunks, W0, m.C, m.shape[1],
                                     value_bytes=vb, chip=ctx.chip)
    cb = ctx.chunk_block if ctx.chunk_block is not None else choice.chunk_block
    wb = ctx.width_block if ctx.width_block is not None else choice.width_block
    if ctx.chunk_block is not None or ctx.width_block is not None:
        # re-claim for the overridden tiling, not the model's choice
        claim = int(KP.vmem_bytes(cb, wb, m.C, m.shape[1], vb))
        choice = PM.BlockChoice(cb, wb, -(-W0 // wb) * wb, claim,
                                hw.vmem_fits(claim, ctx.chip))
    nc = max(1, m.n_chunks)
    while nc % cb:   # nc is fixed by the matrix; cb must divide it
        cb -= 1
    if cb != choice.chunk_block:
        choice = PM.BlockChoice(cb, choice.width_block, choice.width_padded,
                                choice.vmem_bytes, choice.fits_vmem)
    return choice


def _probe_sell_pallas(m, ctx: KernelContext) -> Capability:
    if registry.on_tpu():  # looked up per call: tests fake the platform
        return Capability(False, registry.GATHER_UNSUPPORTED)
    cap = _probe_pallas_dtype(m, ctx)
    if not cap.ok or m is None:
        return cap
    choice = sell_autotune(m, ctx)
    if not choice.fits_vmem:
        return Capability(False, "no (chunk_block, width_block) tiling fits "
                                 "the VMEM budget for this matrix")
    return CAP_OK


def _build_pallas_spmv(m: SELL, ctx: KernelContext, interpret: bool) -> CompiledKernel:
    choice = sell_autotune(m, ctx)
    cb, wb = choice.chunk_block, choice.width_block
    n = m.shape[0]
    limit = hw.vmem_limit(choice.vmem_bytes)

    def kernel(ops, x):
        col3, val3, perm, scale = ops
        tiles = KP.sell_spmv_arrays(col3, val3, x, chunk_block=cb,
                                    width_block=wb, interpret=interpret,
                                    vmem_limit=limit)
        if scale is not None:  # per-chunk scale on the reduced (nc, C) tiles
            tiles = tiles * scale.astype(tiles.dtype)[:, None]
        return KP.sell_spmv_scatter(tiles, perm, n)

    return CompiledKernel(kernel, "pallas-interpret" if interpret else "pallas",
                          choice, to_device(m, *_padded_operands(m, wb)))


def _build_pallas_spmm(m: SELL, ctx: KernelContext, interpret: bool) -> CompiledKernel:
    choice = sell_autotune(m, ctx)
    cb, wb = choice.chunk_block, choice.width_block
    n = m.shape[0]
    vb = int(np.dtype(np.asarray(m.val).dtype).itemsize)

    def kernel(ops, X):
        col3, val3, perm, scale = ops
        # the probe claims VMEM at k=1 (batch width is unknown until call
        # time); X.shape is static per trace, so re-claim here and degrade
        # to the fused XLA formulation on the same wb-padded views when a
        # wide batch would blow the budget — never emit a doomed kernel
        claim = KP.vmem_bytes(cb, wb, m.C, m.shape[1], vb, k=int(X.shape[1]))
        if not hw.vmem_fits(claim, ctx.chip):
            return sell_spmm_padded(col3, val3, perm, X, n, scale)
        tiles = KP.sell_spmm_arrays(col3, val3, X, chunk_block=cb,
                                    width_block=wb, interpret=interpret,
                                    vmem_limit=hw.vmem_limit(claim))
        if scale is not None:
            tiles = tiles * scale.astype(tiles.dtype)[:, None, None]
        return KP.sell_spmm_scatter(tiles, perm, n)

    return CompiledKernel(kernel, "pallas-interpret" if interpret else "pallas",
                          choice, to_device(m, *_padded_operands(m, wb)))


# --- registry entries -------------------------------------------------------


@register_kernel("sell", "spmv", "xla",
                 description="padded-view gather/reduce or flat segment-sum "
                             "(per-container pick) + perm scatter")
def _build_spmv(m: SELL, ctx) -> CompiledKernel:
    from ..core import perfmodel as PM
    n = m.shape[0]
    if PM.sell_xla_uses_flat(m):
        return CompiledKernel(
            functools.partial(_flat_spmv_kernel, n_rows=n,
                              n_segments=m.n_chunks * m.C, C=m.C),
            "xla", operands=to_device(m, *_flat_operands(m)))
    return CompiledKernel(functools.partial(_padded_spmv_kernel, n_rows=n),
                          "xla", operands=to_device(m, *_padded_operands(m)))


@register_kernel("sell", "spmm", "xla",
                 description="padded-view einsum or flat segment-sum "
                             "(per-container pick) + perm scatter")
def _build_spmm(m: SELL, ctx) -> CompiledKernel:
    from ..core import perfmodel as PM
    n = m.shape[0]
    if PM.sell_xla_uses_flat(m):
        return CompiledKernel(
            functools.partial(_flat_spmm_kernel, n_rows=n,
                              n_segments=m.n_chunks * m.C, C=m.C),
            "xla", operands=to_device(m, *_flat_operands(m)))
    return CompiledKernel(functools.partial(_padded_spmm_kernel, n_rows=n),
                          "xla", operands=to_device(m, *_padded_operands(m)))


@register_kernel("sell", "spmv", "loop_reference", auto=False,
                 description="paper-faithful chunk-local slab traversal")
def _build_spmv_loop(m: SELL, ctx) -> CompiledKernel:
    return closure_kernel(lambda x: sell_spmv_loop(m, x), "loop")


@register_kernel("sell", "spmm", "loop_reference", auto=False,
                 description="column-by-column chunk-slab traversals")
def _build_spmm_loop(m: SELL, ctx) -> CompiledKernel:
    return closure_kernel(spmm_by_columns(lambda x: sell_spmv_loop(m, x)),
                          "loop")


@register_kernel("sell", "spmv", "pallas", probe=_probe_sell_pallas,
                 autotune=sell_autotune,
                 description="chunk-slab grid kernel, VMEM-resident x")
def _build_pallas_spmv_compiled(m: SELL, ctx) -> CompiledKernel:
    return _build_pallas_spmv(m, ctx, interpret=False)


@register_kernel("sell", "spmv", "pallas_interpret", probe=_probe_sell_pallas,
                 autotune=sell_autotune,
                 description="chunk-slab grid kernel via the interpreter")
def _build_pallas_spmv_interpret(m: SELL, ctx) -> CompiledKernel:
    return _build_pallas_spmv(m, ctx, interpret=True)


@register_kernel("sell", "spmm", "pallas", probe=_probe_sell_pallas,
                 autotune=sell_autotune,
                 description="multi-vector chunk-slab kernel (one matrix pass)")
def _build_pallas_spmm_compiled(m: SELL, ctx) -> CompiledKernel:
    return _build_pallas_spmm(m, ctx, interpret=False)


@register_kernel("sell", "spmm", "pallas_interpret", probe=_probe_sell_pallas,
                 autotune=sell_autotune,
                 description="multi-vector chunk-slab kernel via the interpreter")
def _build_pallas_spmm_interpret(m: SELL, ctx) -> CompiledKernel:
    return _build_pallas_spmm(m, ctx, interpret=True)
