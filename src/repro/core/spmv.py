"""Back-compat façade over the per-format kernel modules.

The format kernel bodies that used to live here moved to
``repro.kernels.{coo,csr,ell,jds,sell,bsr,dia,hybrid}`` (PR 5: the unified
kernel registry), where each registers its ``(format, op, backend)``
entries with ``repro.kernels.registry``.  This module keeps the historical
``core.spmv`` surface as thin re-exports plus the type-dispatch helpers —
nothing here computes; every consumer reaches the kernels through the
registry (via ``core.plan``) or through these re-exports.

Conventions (unchanged)
-----------------------
* ``x`` is the input vector (paper: ``invec``), ``y`` the result
  (``resvec``).
* All formats compute ``y = A @ x`` for ``A`` of shape ``(M, N)``.
* Multi-vector variants (``spmm``) take ``X`` of shape ``(N, K)``.
"""
from __future__ import annotations

from functools import partial

import jax

from ..kernels import registry as _registry
from ..kernels.bsr import bsr_block_row_ids, bsr_spmm, bsr_spmv  # noqa: F401
from ..kernels.cache import precompute_stats  # noqa: F401
from ..kernels.coo import coo_spmm, coo_spmv  # noqa: F401
from ..kernels.csr import (  # noqa: F401
    csr_row_ids,
    csr_spmm,
    csr_spmv,
    csr_spmv_searchsorted,
)
from ..kernels.dia import (  # noqa: F401
    dia_spmm,
    dia_spmv,
    dia_spmv_loop,
)
from ..kernels.ell import ell_spmm, ell_spmv, ell_spmv_loop  # noqa: F401
from ..kernels.hybrid import hybrid_spmv_loop  # noqa: F401
from ..kernels.jds import (  # noqa: F401
    jds_segment_ids,
    jds_spmm,
    jds_spmv,
    jds_spmv_loop,
)
from ..kernels.sell import (  # noqa: F401
    sell_padded_views,
    sell_spmm,
    sell_spmm_padded,
    sell_spmv,
    sell_spmv_loop,
    sell_spmv_padded,
)
from .formats import BSR, COO, CSR, DIA, ELL, JDS, SELL, HybridDIA

# ---------------------------------------------------------------------------
# type dispatch (format container -> default XLA formulation)
# ---------------------------------------------------------------------------


def _registry_xla(fmt: str, op: str):
    """The registry's XLA entry for ``fmt`` — for formats whose XLA
    formulation exists only as a build hook (hybrid: the sum of its parts'
    entries)."""
    return lambda matrix, x: _registry.build(matrix, fmt, op, "xla").fn(x)


_DISPATCH = {
    COO: coo_spmv,
    CSR: csr_spmv,
    ELL: ell_spmv,
    JDS: jds_spmv,
    SELL: sell_spmv,
    BSR: bsr_spmv,
    DIA: dia_spmv,
    HybridDIA: _registry_xla("hybrid", "spmv"),
}

_DISPATCH_MM = {
    COO: coo_spmm,
    CSR: csr_spmm,
    ELL: ell_spmm,
    JDS: jds_spmm,
    SELL: sell_spmm,
    BSR: bsr_spmm,
    DIA: dia_spmm,
    HybridDIA: _registry_xla("hybrid", "spmm"),
}


def spmv(matrix, x) -> "jax.Array":
    """Format-dispatching SpMV (reference path)."""
    fn = _DISPATCH.get(type(matrix))
    if fn is None:
        raise TypeError(f"no spmv for {type(matrix).__name__}")
    return fn(matrix, x)


def spmm(matrix, X) -> "jax.Array":
    """Format-dispatching multi-vector SpMV: X (N, K) -> Y (M, K)."""
    fn = _DISPATCH_MM.get(type(matrix))
    if fn is None:
        raise TypeError(f"no spmm for {type(matrix).__name__}")
    return fn(matrix, X)


#: the pre-plan formulations (per-call row-id expansion, host-unrolled
#: chunk/diagonal loops) — the "naive" side of plan-vs-naive benchmarks
_DISPATCH_NAIVE = {
    **_DISPATCH,
    CSR: csr_spmv_searchsorted,
    JDS: jds_spmv_loop,
    SELL: sell_spmv_loop,
    DIA: dia_spmv_loop,
    HybridDIA: hybrid_spmv_loop,
}


def naive_spmv(matrix, x) -> "jax.Array":
    """SpMV via the legacy per-call formulations (benchmark baseline)."""
    fn = _DISPATCH_NAIVE.get(type(matrix))
    if fn is None:
        raise TypeError(f"no spmv for {type(matrix).__name__}")
    return fn(matrix, x)


def make_naive_spmv(matrix, jit: bool = True):
    """Naive-baseline counterpart of ``make_spmv`` (benchmarks only)."""
    fn = partial(naive_spmv, matrix)
    return jax.jit(fn) if jit else fn


def make_spmv(matrix, jit: bool = True):
    """Close over the concrete matrix and return ``f(x) -> y``.

    Host metadata (chunk/diag pointers) becomes static structure; the arrays
    become constants embedded in the jaxpr — the right trade for a matrix
    reused across many SpMVs (the paper's eigensolver setting).  For the
    fully preprocessed + autotuned execution path use
    ``repro.core.plan.SpMVPlan.compile`` instead.
    """
    fn = partial(spmv, matrix)
    return jax.jit(fn) if jit else fn


def flops_of(matrix) -> int:
    """Useful FLOPs of one SpMV: 2 per stored non-zero (mul+add).

    For BSR this counts the *dense block* entries (the format trades useless
    flops for MXU regularity — the model accounts for it the same way).
    """
    return 2 * matrix.nnz
