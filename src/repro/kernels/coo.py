"""COO kernels: the interchange format's gather + segment-sum formulation.

Registry entries: ``(coo, {spmv, spmm}, {xla, loop_reference})``.  The
loop-reference oracle uses an index-scatter (``.at[rows].add``) instead of
``segment_sum`` so the two entries share no reduction code path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.formats import COO
from .accum import acc_dtype
from .cache import spmm_by_columns, to_device
from .registry import CompiledKernel, closure_kernel, register_kernel


def _operands(m: COO) -> tuple:
    return m.vals, m.cols, m.rows, m.scale


def coo_spmv_arrays(ops, x: jnp.ndarray, n_rows: int) -> jnp.ndarray:
    vals, cols, rows, scale = ops
    acc = acc_dtype(vals.dtype, x.dtype)
    prod = jnp.asarray(vals).astype(acc) * jnp.take(x, cols, axis=0).astype(acc)
    y = jax.ops.segment_sum(prod, rows, num_segments=n_rows)
    if scale is not None:
        y = y * jnp.asarray(scale).astype(acc)
    return y


def coo_spmm_arrays(ops, X: jnp.ndarray, n_rows: int) -> jnp.ndarray:
    vals, cols, rows, scale = ops
    acc = acc_dtype(vals.dtype, X.dtype)
    prod = (jnp.asarray(vals).astype(acc)[:, None]
            * jnp.take(X, cols, axis=0).astype(acc))
    Y = jax.ops.segment_sum(prod, rows, num_segments=n_rows)
    if scale is not None:
        Y = Y * jnp.asarray(scale).astype(acc)[:, None]
    return Y


def coo_spmv(m: COO, x: jnp.ndarray) -> jnp.ndarray:
    return coo_spmv_arrays(_operands(m), x, m.shape[0])


def coo_spmm(m: COO, X: jnp.ndarray) -> jnp.ndarray:
    return coo_spmm_arrays(_operands(m), X, m.shape[0])


def coo_spmv_scatter(m: COO, x: jnp.ndarray) -> jnp.ndarray:
    """Scatter-add formulation — the loop-reference oracle."""
    acc = acc_dtype(jnp.asarray(m.vals).dtype, x.dtype)
    prod = (jnp.asarray(m.vals).astype(acc)
            * jnp.take(x, jnp.asarray(m.cols), axis=0).astype(acc))
    y = jnp.zeros(m.shape[0], dtype=acc)
    y = y.at[jnp.asarray(m.rows)].add(prod)
    if m.scale is not None:
        y = y * jnp.asarray(m.scale).astype(acc)
    return y


# --- registry entries -------------------------------------------------------


@register_kernel("coo", "spmv", "xla",
                 description="gather + segment-sum over explicit row ids")
def _build_spmv(m: COO, ctx) -> CompiledKernel:
    return CompiledKernel(functools.partial(coo_spmv_arrays, n_rows=m.shape[0]),
                          "xla", operands=to_device(m, *_operands(m)))


@register_kernel("coo", "spmm", "xla",
                 description="multi-vector gather + segment-sum")
def _build_spmm(m: COO, ctx) -> CompiledKernel:
    return CompiledKernel(functools.partial(coo_spmm_arrays, n_rows=m.shape[0]),
                          "xla", operands=to_device(m, *_operands(m)))


@register_kernel("coo", "spmv", "loop_reference", auto=False,
                 description="independent scatter-add oracle")
def _build_spmv_loop(m: COO, ctx) -> CompiledKernel:
    return closure_kernel(lambda x: coo_spmv_scatter(m, x), "loop")


@register_kernel("coo", "spmm", "loop_reference", auto=False,
                 description="column-by-column scatter-add oracle")
def _build_spmm_loop(m: COO, ctx) -> CompiledKernel:
    return closure_kernel(spmm_by_columns(lambda x: coo_spmv_scatter(m, x)),
                          "loop")
