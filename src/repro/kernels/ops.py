"""Public kernel entry points with backend dispatch.

backend:
  "pallas"  — the Pallas kernels (interpret=True off-TPU, compiled on TPU);
  "ref"     — the pure-jnp formulations (XLA-fused; the fast path on CPU);
  "auto"    — capability probes + roofline ranking via the registry.

This module predates ``registry`` and is kept as a thin convenience shim:
every function below resolves to a registry entry (``repro.kernels.
registry``), so a single table drives the whole framework — these wrappers
only translate the legacy backend names and jit the result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.formats import BSR, DIA, SELL, HybridDIA
from . import moe_gemm as _moe
from . import ref as _ref
from . import registry as R


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(backend: str) -> str:
    """Legacy name -> registry backend ("auto" stays symbolic)."""
    if backend == "auto":
        return "auto"
    if backend == "ref":
        return "xla"
    if backend == "pallas":
        return "pallas" if on_tpu() else "pallas_interpret"
    return backend


def _interpret() -> bool:
    return not on_tpu()


def _build(matrix, fmt: str, op: str, backend: str, **ctx_kw):
    ctx = R.KernelContext(**ctx_kw)
    be = _resolve(backend)
    if be == "auto":
        return R.build_best(matrix, fmt, op, ctx)
    try:
        return R.build(matrix, fmt, op, be, ctx)
    except (KeyError, R.BackendUnavailable):
        # degrade like the plan layer: an explicitly requested backend that
        # cannot run this operand compiles the XLA formulation instead
        return R.build(matrix, fmt, op, "xla", ctx)


# ---------------------------------------------------------------------------
# SELL
# ---------------------------------------------------------------------------


def make_sell_spmv(m: SELL, *, backend: str = "auto", chunk_block: int | None = None,
                   width_pad: int | None = None):
    """Returns jitted ``f(x) -> y`` for a concrete SELL matrix.

    Delegates to the plan layer — one compile pipeline (registry dispatch,
    autotune hook, VMEM-fit fallback, cached padded views) for both entry
    points.
    """
    from ..core.plan import SpMVPlan
    from ..core.planconfig import PlanConfig

    plan = SpMVPlan.compile(m, PlanConfig(backend=backend,
                                          chunk_block=chunk_block,
                                          width_block=width_pad))
    return functools.partial(plan.kernel, plan.operands)


# ---------------------------------------------------------------------------
# BSR
# ---------------------------------------------------------------------------


def make_bsr_spmm(m: BSR, *, backend: str = "auto"):
    return _build(m, "bsr", "spmm", backend).jitted()


# ---------------------------------------------------------------------------
# DIA / Hybrid
# ---------------------------------------------------------------------------


def make_dia_spmv(m: DIA, *, backend: str = "auto", tile: int | None = None):
    return _build(m, "dia", "spmv", backend, tile=tile).jitted()


def make_hybrid_spmv(m: HybridDIA, *, backend: str = "auto",
                     chunk_block: int | None = None,
                     width_pad: int | None = None):
    return _build(m, "hybrid", "spmv", backend, chunk_block=chunk_block,
                  width_block=width_pad).jitted()


# ---------------------------------------------------------------------------
# grouped GEMM
# ---------------------------------------------------------------------------


def grouped_gemm(X, expert_of_token, W, *, backend: str = "auto", bt: int = 128):
    # not a registry format (MoE GEMM, no loop oracle); keep the historical
    # two-path dispatch: only "pallas" (or "auto" on TPU) takes the kernel,
    # every other name runs the reference path
    be = "pallas" if (backend == "pallas"
                      or (backend == "auto" and on_tpu())) else "ref"
    if be == "pallas":
        return _moe.grouped_gemm(X, expert_of_token, W, bt=bt, interpret=_interpret())
    order, inv, tile_expert, T_pad = _moe.plan_groups(
        np.asarray(expert_of_token), W.shape[0], bt)
    Xp = jnp.zeros((T_pad, X.shape[1]), X.dtype).at[jnp.asarray(inv)].set(X)
    Yp = _ref.grouped_gemm_ref(jnp.asarray(tile_expert), Xp, W, bt)
    return jnp.take(Yp, jnp.asarray(inv), axis=0)


# ---------------------------------------------------------------------------
# format-level dispatch (mirrors core.spmv.make_spmv but registry-backed)
# ---------------------------------------------------------------------------

_FMT_OF = {SELL: "sell", BSR: "bsr", DIA: "dia", HybridDIA: "hybrid"}


def make_kernel_spmv(matrix, *, backend: str = "auto", **kw):
    if isinstance(matrix, SELL):
        return make_sell_spmv(matrix, backend=backend, **kw)
    if isinstance(matrix, HybridDIA):
        return make_hybrid_spmv(matrix, backend=backend)
    fmt = _FMT_OF.get(type(matrix))
    if fmt is None:
        raise TypeError(f"no kernel path for {type(matrix).__name__}")
    return _build(matrix, fmt, "spmv", backend, **kw).jitted()
