"""JDS kernels (paper's jagged diagonals: sparse vector triad, 18 B/F).

Registry entries: ``(jds, {spmv, spmm}, {xla, loop_reference})``.  The
loop-reference oracle is the paper-faithful per-jagged-diagonal traversal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.formats import JDS
from .accum import acc_dtype
from .cache import cached, register_stat, spmm_by_columns, to_device
from .registry import CompiledKernel, closure_kernel, register_kernel

register_stat("jds_segment_ids")


def jds_segment_ids(m: JDS) -> jnp.ndarray:
    """Permuted-row id per stored element: within jagged diagonal d the k-th
    entry belongs to permuted row k.  Built host-side once and cached."""

    def build():
        jp = np.asarray(m.jd_ptr, dtype=np.int64)
        lens = np.diff(jp)
        ids = np.arange(int(jp[-1]), dtype=np.int64) - np.repeat(jp[:-1], lens)
        return ids.astype(np.int32)

    return cached(m, "_segment_ids", "jds_segment_ids", build)


def _operands(m: JDS) -> tuple:
    n_rows = m.shape[0]
    return (m.val, m.col_idx, jds_segment_ids(m), np.asarray(m.perm)[:n_rows],
            m.scale)


def jds_spmv_arrays(ops, x: jnp.ndarray, n_perm: int) -> jnp.ndarray:
    """Vectorized JDS: one gather + one segment-sum over the precomputed
    permuted-row table, then the perm-scatter back to original order."""
    val, col, seg, perm, scale = ops
    acc = acc_dtype(val.dtype, x.dtype)
    prod = jnp.asarray(val).astype(acc) * jnp.take(x, col, axis=0).astype(acc)
    y_perm = jax.ops.segment_sum(prod, seg, num_segments=n_perm)
    if scale is not None:  # per-*permuted*-row scale, before the scatter
        y_perm = y_perm * jnp.asarray(scale).astype(acc)
    n_rows = perm.shape[0]
    y = jnp.zeros(n_rows, dtype=y_perm.dtype)
    return y.at[perm].set(y_perm[:n_rows])


def jds_spmm_arrays(ops, X: jnp.ndarray, n_perm: int) -> jnp.ndarray:
    val, col, seg, perm, scale = ops
    acc = acc_dtype(val.dtype, X.dtype)
    prod = (jnp.asarray(val).astype(acc)[:, None]
            * jnp.take(X, col, axis=0).astype(acc))
    Y_perm = jax.ops.segment_sum(prod, seg, num_segments=n_perm)
    if scale is not None:
        Y_perm = Y_perm * jnp.asarray(scale).astype(acc)[:, None]
    n_rows = perm.shape[0]
    Y = jnp.zeros((n_rows, X.shape[1]), dtype=Y_perm.dtype)
    return Y.at[perm].set(Y_perm[:n_rows])


def _n_perm(m: JDS) -> int:
    return int(np.asarray(m.perm).shape[0])


def jds_spmv(m: JDS, x: jnp.ndarray) -> jnp.ndarray:
    return jds_spmv_arrays(_operands(m), x, _n_perm(m))


def jds_spmm(m: JDS, X: jnp.ndarray) -> jnp.ndarray:
    return jds_spmm_arrays(_operands(m), X, _n_perm(m))


def jds_spmv_loop(m: JDS, x: jnp.ndarray) -> jnp.ndarray:
    """Faithful JDS traversal: one pass per jagged diagonal (paper's outer
    loop).  Kept as the paper-fidelity oracle; traces O(n_diags) segments."""
    jp = np.asarray(m.jd_ptr)
    n_rows = m.shape[0]
    n_pad = int(np.asarray(m.perm).shape[0])
    acc = acc_dtype(jnp.asarray(m.val).dtype, x.dtype)
    y_perm = jnp.zeros(n_pad, dtype=acc)
    val = jnp.asarray(m.val).astype(acc)
    ci = jnp.asarray(m.col_idx)
    for d in range(m.n_diags):
        lo, hi = int(jp[d]), int(jp[d + 1])
        seg_val = val[lo:hi]
        seg_x = jnp.take(x, ci[lo:hi], axis=0).astype(acc)
        y_perm = y_perm.at[: hi - lo].add(seg_val * seg_x)
    if m.scale is not None:
        y_perm = y_perm * jnp.asarray(m.scale).astype(acc)
    y = jnp.zeros(n_rows, dtype=y_perm.dtype)
    return y.at[jnp.asarray(m.perm)[:n_rows]].set(y_perm[:n_rows])


# --- registry entries -------------------------------------------------------


@register_kernel("jds", "spmv", "xla",
                 description="gather + segment-sum over permuted-row table")
def _build_spmv(m: JDS, ctx) -> CompiledKernel:
    return CompiledKernel(functools.partial(jds_spmv_arrays, n_perm=_n_perm(m)),
                          "xla", operands=to_device(m, *_operands(m)))


@register_kernel("jds", "spmm", "xla",
                 description="multi-vector permuted segment-sum")
def _build_spmm(m: JDS, ctx) -> CompiledKernel:
    return CompiledKernel(functools.partial(jds_spmm_arrays, n_perm=_n_perm(m)),
                          "xla", operands=to_device(m, *_operands(m)))


@register_kernel("jds", "spmv", "loop_reference", auto=False,
                 description="paper-faithful per-jagged-diagonal traversal")
def _build_spmv_loop(m: JDS, ctx) -> CompiledKernel:
    return closure_kernel(lambda x: jds_spmv_loop(m, x), "loop")


@register_kernel("jds", "spmm", "loop_reference", auto=False,
                 description="column-by-column jagged-diagonal traversals")
def _build_spmm_loop(m: JDS, ctx) -> CompiledKernel:
    return closure_kernel(spmm_by_columns(lambda x: jds_spmv_loop(m, x)),
                          "loop")
