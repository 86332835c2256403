"""Hybrid DIA+SELL kernels: composition of the two part registries.

Registry entries: ``(hybrid, {spmv, spmm}, {xla, loop_reference})`` plus a
``{pallas, pallas_interpret}`` SpMV that composes the DIA and SELL Pallas
kernels (no Pallas SpMM: the DIA part has none — the plan layer falls back
to the XLA formulation for multi-vector hybrid execution).  The XLA entries
are the sum of the DIA and SELL parts' own XLA entries, so the SELL part
takes the padded or flat formulation by ``perfmodel.sell_xla_uses_flat``,
as a SELL matrix would.  On TPU the
Pallas entry is refused with its SELL part's reason: the SELL kernel does
not lower there.
"""
from __future__ import annotations

import jax
import numpy as np

from ..core.formats import HybridDIA
from . import dia as KD
from . import sell as KS
from .cache import spmm_by_columns
from .registry import Capability, CompiledKernel, KernelContext, closure_kernel, register_kernel


def hybrid_spmv_loop(m: HybridDIA, x):
    return KD.dia_spmv_loop(m.dia, x) + KS.sell_spmv_loop(m.rest, x)


def _sum_of(ck_d: CompiledKernel, ck_s: CompiledKernel, label: str,
            choice=None) -> CompiledKernel:
    """The DIA and SELL parts' executors summed; each keeps its operands.
    The parts trace under the name scopes ``dia`` and ``sell``, which name
    their operations in the program's metadata and change no code."""
    def kernel(ops, x):
        with jax.named_scope("dia"):
            y_dia = ck_d.kernel(ops[0], x)
        with jax.named_scope("sell"):
            y_sell = ck_s.kernel(ops[1], x)
        return y_dia + y_sell
    return CompiledKernel(kernel, label, choice, (ck_d.operands, ck_s.operands))


# --- registry entries -------------------------------------------------------


@register_kernel("hybrid", "spmv", "xla",
                 description="DIA shifted slices + SELL (padded or flat) sum")
def _build_spmv(m: HybridDIA, ctx) -> CompiledKernel:
    return _sum_of(KD._build_spmv(m.dia, ctx), KS._build_spmv(m.rest, ctx),
                   "xla")


@register_kernel("hybrid", "spmm", "xla",
                 description="multi-vector DIA + SELL composition")
def _build_spmm(m: HybridDIA, ctx) -> CompiledKernel:
    return _sum_of(KD._build_spmm(m.dia, ctx), KS._build_spmm(m.rest, ctx),
                   "xla")


@register_kernel("hybrid", "spmv", "loop_reference", auto=False,
                 description="per-diagonal + per-chunk traversal oracles")
def _build_spmv_loop(m: HybridDIA, ctx) -> CompiledKernel:
    return closure_kernel(lambda x: hybrid_spmv_loop(m, x), "loop")


@register_kernel("hybrid", "spmm", "loop_reference", auto=False,
                 description="column-by-column composed traversals")
def _build_spmm_loop(m: HybridDIA, ctx) -> CompiledKernel:
    return closure_kernel(spmm_by_columns(lambda x: hybrid_spmv_loop(m, x)),
                          "loop")


def _probe_hybrid_pallas(m, ctx: KernelContext) -> Capability:
    if m is None:
        return KS._probe_sell_pallas(None, ctx)
    # an empty DIA part is fine here (the SELL remainder carries everything,
    # and the build composes a zeros closure for the DIA half)
    if int(np.asarray(m.dia.offsets).shape[0]):
        cap_d = KD._probe_dia_pallas(m.dia, ctx)
        if not cap_d.ok:
            return cap_d
    return KS._probe_sell_pallas(m.rest, ctx)


def _build_hybrid_pallas(m: HybridDIA, ctx: KernelContext, interpret: bool) -> CompiledKernel:
    ck_d = KD._build_dia_pallas(m.dia, ctx, interpret)
    if not m.rest.nnz:
        return ck_d
    ck_s = KS._build_pallas_spmv(m.rest, ctx, interpret)
    return _sum_of(ck_d, ck_s, ck_s.label, ck_s.choice)


@register_kernel("hybrid", "spmv", "pallas", probe=_probe_hybrid_pallas,
                 description="composed DIA + SELL Pallas kernels")
def _build_pallas_compiled(m: HybridDIA, ctx) -> CompiledKernel:
    return _build_hybrid_pallas(m, ctx, interpret=False)


@register_kernel("hybrid", "spmv", "pallas_interpret",
                 probe=_probe_hybrid_pallas,
                 description="composed DIA + SELL kernels via the interpreter")
def _build_pallas_interpret(m: HybridDIA, ctx) -> CompiledKernel:
    return _build_hybrid_pallas(m, ctx, interpret=True)
