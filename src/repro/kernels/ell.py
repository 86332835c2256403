"""ELL kernels (padded jagged; the vectorizable building block).

Registry entries: ``(ell, {spmv, spmm}, {xla, loop_reference})``.  The
loop-reference oracle walks the padded width one jagged column at a time —
the paper's JDS traversal restricted to the unpermuted padded layout.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..core.formats import ELL
from .accum import acc_dtype
from .cache import spmm_by_columns, to_device
from .registry import CompiledKernel, closure_kernel, register_kernel


def _operands(m: ELL) -> tuple:
    return m.val, m.col_idx, m.scale


def ell_spmv_arrays(ops, x: jnp.ndarray) -> jnp.ndarray:
    """Row-major ELL: one gather of shape (M, W), one reduction over W.
    Reduces in ``acc_dtype`` (>= f32); a quantized container's per-row
    scale is applied to the reduced row sums."""
    val, col, scale = ops
    acc = acc_dtype(val.dtype, x.dtype)
    gathered = jnp.take(x, col, axis=0)  # (M, W)
    y = jnp.sum(jnp.asarray(val).astype(acc) * gathered.astype(acc), axis=1)
    if scale is not None:
        y = y * jnp.asarray(scale).astype(acc)
    return y


def ell_spmm_arrays(ops, X: jnp.ndarray) -> jnp.ndarray:
    val, col, scale = ops
    acc = acc_dtype(val.dtype, X.dtype)
    gathered = jnp.take(X, col, axis=0)  # (M, W, K)
    Y = jnp.einsum("mw,mwk->mk", jnp.asarray(val).astype(acc),
                   gathered.astype(acc))
    if scale is not None:
        Y = Y * jnp.asarray(scale).astype(acc)[:, None]
    return Y


def ell_spmv(m: ELL, x: jnp.ndarray) -> jnp.ndarray:
    return ell_spmv_arrays(_operands(m), x)


def ell_spmm(m: ELL, X: jnp.ndarray) -> jnp.ndarray:
    return ell_spmm_arrays(_operands(m), X)


def ell_spmv_loop(m: ELL, x: jnp.ndarray) -> jnp.ndarray:
    """One pass per padded jagged column (host loop over W)."""
    col = jnp.asarray(m.col_idx)
    acc = acc_dtype(jnp.asarray(m.val).dtype, x.dtype)
    val = jnp.asarray(m.val).astype(acc)
    y = jnp.zeros(m.shape[0], dtype=acc)
    for j in range(m.width):
        y = y + val[:, j] * jnp.take(x, col[:, j], axis=0).astype(acc)
    if m.scale is not None:
        y = y * jnp.asarray(m.scale).astype(acc)
    return y


# --- registry entries -------------------------------------------------------


@register_kernel("ell", "spmv", "xla",
                 description="one (M, W) gather + width reduction")
def _build_spmv(m: ELL, ctx) -> CompiledKernel:
    return CompiledKernel(ell_spmv_arrays, "xla",
                          operands=to_device(m, *_operands(m)))


@register_kernel("ell", "spmm", "xla",
                 description="(M, W, K) gather + einsum")
def _build_spmm(m: ELL, ctx) -> CompiledKernel:
    return CompiledKernel(ell_spmm_arrays, "xla",
                          operands=to_device(m, *_operands(m)))


@register_kernel("ell", "spmv", "loop_reference", auto=False,
                 description="per-jagged-column traversal oracle")
def _build_spmv_loop(m: ELL, ctx) -> CompiledKernel:
    return closure_kernel(lambda x: ell_spmv_loop(m, x), "loop")


@register_kernel("ell", "spmm", "loop_reference", auto=False,
                 description="column-by-column jagged-traversal oracle")
def _build_spmm_loop(m: ELL, ctx) -> CompiledKernel:
    return closure_kernel(spmm_by_columns(lambda x: ell_spmv_loop(m, x)),
                          "loop")
