"""CSR kernels (paper's CRS: inner loop = sparse scalar product, 10 B/F).

Registry entries: ``(csr, {spmv, spmm}, {xla, loop_reference, pallas,
pallas_interpret})`` — the Pallas backend is the row-split kernel of
``csr_spmv.py``.  The loop-reference oracle is the legacy per-call
formulation (on-device searchsorted row-id expansion), independent of the
cached-row-ids fast path it validates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.formats import CSR
from ..utils import hw
from . import csr_spmv as KP
from .accum import acc_dtype
from .cache import cached, is_traced, register_stat, spmm_by_columns, to_device
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

register_stat("csr_row_ids")


def csr_row_ids(m: CSR) -> jnp.ndarray:
    """Expand row_ptr to one row id per nnz.

    Host-computed once and cached on the container; falls back to the
    on-device searchsorted expansion when the container holds tracers
    (matrix passed as a jit argument instead of a closure constant).
    """
    if is_traced(m.row_ptr):
        nnz = int(np.asarray(m.col_idx.shape)[0]) if not is_traced(m.col_idx) else m.col_idx.shape[0]
        return (
            jnp.searchsorted(
                jnp.asarray(m.row_ptr), jnp.arange(nnz, dtype=jnp.int32), side="right"
            ).astype(jnp.int32)
            - 1
        )

    def build():
        rp = np.asarray(m.row_ptr, dtype=np.int64)
        return np.repeat(np.arange(len(rp) - 1, dtype=np.int32), np.diff(rp))

    return cached(m, "_row_ids", "csr_row_ids", build)


def _operands(m: CSR) -> tuple:
    """(val, col, row ids, scale): the arrays the CRS kernels stream."""
    return m.val, m.col_idx, csr_row_ids(m), m.scale


def csr_spmv_arrays(ops, x: jnp.ndarray, n_rows: int) -> jnp.ndarray:
    """Gather + segment-sum formulation of the CRS kernel.

    Products and the segment reduction run in ``acc_dtype`` (>= f32); a
    quantized container's per-row scale is applied to the *reduced* row
    sums, so only the narrow value array is streamed per element."""
    val, col, rid, scale = ops
    acc = acc_dtype(val.dtype, x.dtype)
    prod = jnp.asarray(val).astype(acc) * jnp.take(x, col, axis=0).astype(acc)
    y = jax.ops.segment_sum(prod, rid, num_segments=n_rows)
    if scale is not None:
        y = y * jnp.asarray(scale).astype(acc)
    return y


def csr_spmm_arrays(ops, X: jnp.ndarray, n_rows: int) -> jnp.ndarray:
    val, col, rid, scale = ops
    acc = acc_dtype(val.dtype, X.dtype)
    prod = (jnp.asarray(val).astype(acc)[:, None]
            * jnp.take(X, col, axis=0).astype(acc))
    Y = jax.ops.segment_sum(prod, rid, num_segments=n_rows)
    if scale is not None:
        Y = Y * jnp.asarray(scale).astype(acc)[:, None]
    return Y


def csr_spmv(m: CSR, x: jnp.ndarray) -> jnp.ndarray:
    return csr_spmv_arrays(_operands(m), x, m.shape[0])


def csr_spmv_searchsorted(m: CSR, x: jnp.ndarray) -> jnp.ndarray:
    """Legacy CRS formulation: the row-id expansion runs on device on every
    call (an O(nnz log n) searchsorted the cached path amortizes away).
    Kept as the naive baseline for plan-vs-naive benchmarks and as the
    registry's loop-reference oracle."""
    nnz = int(np.asarray(m.col_idx).shape[0])
    row_ids = (
        jnp.searchsorted(
            jnp.asarray(m.row_ptr), jnp.arange(nnz, dtype=jnp.int32), side="right"
        ).astype(jnp.int32)
        - 1
    )
    acc = acc_dtype(jnp.asarray(m.val).dtype, x.dtype)
    prod = (jnp.asarray(m.val).astype(acc)
            * jnp.take(x, jnp.asarray(m.col_idx), axis=0).astype(acc))
    y = jax.ops.segment_sum(prod, row_ids, num_segments=m.shape[0])
    if m.scale is not None:
        y = y * jnp.asarray(m.scale).astype(acc)
    return y


def csr_spmm(m: CSR, X: jnp.ndarray) -> jnp.ndarray:
    return csr_spmm_arrays(_operands(m), X, m.shape[0])


# --- registry entries -------------------------------------------------------


@register_kernel("csr", "spmv", "xla",
                 description="cached row-ids gather + segment-sum")
def _build_spmv(m: CSR, ctx: KernelContext) -> CompiledKernel:
    return CompiledKernel(functools.partial(csr_spmv_arrays, n_rows=m.shape[0]),
                          "xla", operands=to_device(m, *_operands(m)))


@register_kernel("csr", "spmm", "xla",
                 description="multi-vector cached row-ids segment-sum")
def _build_spmm(m: CSR, ctx: KernelContext) -> CompiledKernel:
    return CompiledKernel(functools.partial(csr_spmm_arrays, n_rows=m.shape[0]),
                          "xla", operands=to_device(m, *_operands(m)))


@register_kernel("csr", "spmv", "loop_reference", auto=False,
                 description="per-call searchsorted row-id expansion (naive oracle)")
def _build_spmv_loop(m: CSR, ctx: KernelContext) -> CompiledKernel:
    return closure_kernel(lambda x: csr_spmv_searchsorted(m, x), "loop")


@register_kernel("csr", "spmm", "loop_reference", auto=False,
                 description="column-by-column naive-oracle SpMVs")
def _build_spmm_loop(m: CSR, ctx: KernelContext) -> CompiledKernel:
    return closure_kernel(
        spmm_by_columns(lambda x: csr_spmv_searchsorted(m, x)), "loop")


def _rowsplit_geometry(ctx: KernelContext) -> tuple[int, int]:
    R = ctx.width_block if ctx.width_block is not None else 8
    tb = ctx.chunk_block if ctx.chunk_block is not None else 8
    return R, tb


def csr_rowsplit_autotune(m: CSR, ctx: KernelContext):
    """Registry autotune hook: the slab geometry + its VMEM claim.

    Uses the O(n) geometry computation — probing must stay cheap (auto
    selection probes every entry, including ones that then lose), so the
    full (T, E) slab build is deferred to the build hook.
    """
    R, tb = _rowsplit_geometry(ctx)
    T, E = KP.csr_rowsplit_geometry(m, R=R, tile_block=tb)
    vb = np.dtype(np.asarray(m.val).dtype).itemsize
    claim = KP.rowsplit_vmem_bytes(tb, E, R, m.shape[1], vb)
    return {"R": R, "tile_block": tb, "tiles": T, "tile_nnz_padded": E,
            "vmem_bytes": int(claim),
            "fits_vmem": hw.vmem_fits(claim, ctx.chip)}


def _probe_rowsplit(m, ctx: KernelContext) -> Capability:
    if registry.on_tpu():  # looked up per call: tests fake the platform
        return Capability(False, registry.GATHER_UNSUPPORTED)
    cap = _probe_pallas_dtype(m, ctx)
    if not cap.ok or m is None:
        return cap
    tune = csr_rowsplit_autotune(m, ctx)
    if not tune["fits_vmem"]:
        return Capability(False, "row-split slab tiling exceeds the VMEM budget")
    return CAP_OK


def _build_rowsplit(m: CSR, ctx: KernelContext, interpret: bool) -> CompiledKernel:
    R, tb = _rowsplit_geometry(ctx)
    col2, val2, rid2, T, E = KP.csr_rowsplit_prepare(m, R=R, tile_block=tb)
    tune = csr_rowsplit_autotune(m, ctx)
    limit = hw.vmem_limit(tune["vmem_bytes"])

    def kernel(ops, x):
        col2, val2, rid2, scale = ops
        y = KP.csr_rowsplit_arrays(col2, val2, rid2, x, R=R, tile_block=tb,
                                   interpret=interpret, vmem_limit=limit)
        y = y.reshape(-1)[:m.n_rows]
        # per-row scale applies to the finished row sums, outside the kernel
        return y if scale is None else y * scale.astype(y.dtype)

    return CompiledKernel(kernel, "pallas-interpret" if interpret else "pallas",
                          tune, to_device(m, col2, val2, rid2, m.scale))


@register_kernel("csr", "spmv", "pallas", probe=_probe_rowsplit,
                 autotune=csr_rowsplit_autotune,
                 description="row-split slab kernel, one-hot tile reduce")
def _build_rowsplit_compiled(m: CSR, ctx: KernelContext) -> CompiledKernel:
    return _build_rowsplit(m, ctx, interpret=False)


@register_kernel("csr", "spmv", "pallas_interpret", probe=_probe_rowsplit,
                 autotune=csr_rowsplit_autotune,
                 description="row-split slab kernel via the interpreter")
def _build_rowsplit_interpret(m: CSR, ctx: KernelContext) -> CompiledKernel:
    return _build_rowsplit(m, ctx, interpret=True)
