"""Kernel-registry parity suite + capability-probe behavior.

Every registered ``(format, op, backend)`` entry is validated against the
``loop_reference`` backend of the same format — the paper-fidelity
traversal oracles — across corpus matrices spanning ≥ 6 regimes and both
{float32, float64} dtypes.  Unsupported combinations (compiled Pallas off
TPU, f64 through the TPU-targeted kernels, tilings that cannot fit VMEM)
must be *skipped via their probes*, never crash.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import corpus
from repro.core import formats as F
from repro.core.plan import SpMVPlan
from repro.kernels import registry as R

#: designated corpus workload per format — collectively 7 corpus matrices
#: (holstein_exact, random_uniform, stripe, powerlaw, banded_narrow,
#: blocksparse, holstein_surrogate) spanning the paper's regimes
PARITY_MATRIX = {
    "csr": "holstein_exact",
    "coo": "random_uniform",
    "ell": "stripe",
    "jds": "powerlaw",
    "sell": "powerlaw",
    "dia": "banded_narrow",
    "bsr": "blocksparse",
    "hybrid": "holstein_surrogate",
}

DTYPES = (np.float32, np.float64)

_CONTAINERS: dict = {}
_ORACLES: dict = {}


def _x64_ctx(dtype):
    if dtype == np.float64:
        return jax.enable_x64(True)
    import contextlib
    return contextlib.nullcontext()


def _container(fmt: str, dtype):
    """A fresh converted container per (format, dtype) — containers carry
    build-once caches, so dtypes must not share one."""
    key = (fmt, np.dtype(dtype).name)
    if key in _CONTAINERS:
        return _CONTAINERS[key]
    spec = corpus.get(PARITY_MATRIX[fmt])
    src = corpus.build(spec.name)
    m = F.CSR(np.asarray(src.row_ptr), np.asarray(src.col_idx),
              np.asarray(src.val).astype(dtype), src.shape)
    if fmt == "csr":
        obj = m
    elif fmt == "coo":
        obj = m.to_coo()
    elif fmt == "sell":
        obj = F.SELL.from_csr(m, **spec.sell_kwargs())
    elif fmt == "hybrid":
        obj = F.split_dia(m, C=spec.sell_C, sigma=spec.sell_sigma)
    elif fmt == "bsr":
        obj = F.BSR.from_dense(m.to_dense(), (8, 128))
    elif fmt == "dia":
        obj = F.DIA.from_csr(m)
    else:
        obj = F.convert(m, fmt)
    _CONTAINERS[key] = obj
    return obj


def _operand(obj, op: str, dtype, k: int = 3):
    rng = np.random.default_rng(0)
    n = obj.shape[1]
    shape = (n,) if op == "spmv" else (n, k)
    return rng.standard_normal(shape).astype(dtype)


def _oracle(fmt: str, op: str, dtype):
    """loop_reference output, computed eagerly, cached per (fmt, op, dtype)."""
    key = (fmt, op, np.dtype(dtype).name)
    if key in _ORACLES:
        return _ORACLES[key]
    obj = _container(fmt, dtype)
    with _x64_ctx(dtype):
        fn = R.build(obj, fmt, op, "loop_reference").fn
        out = np.asarray(fn(jnp.asarray(_operand(obj, op, dtype))))
    _ORACLES[key] = out
    return out


def _parity_cases():
    cases = []
    for e in R.entries():
        if e.format not in PARITY_MATRIX or e.backend == "loop_reference":
            continue
        for dtype in DTYPES:
            cases.append(pytest.param(
                e.format, e.op, e.backend, dtype,
                id=f"{e.format}-{e.op}-{e.backend}-{np.dtype(dtype).name}"))
    return cases


@pytest.mark.parametrize("fmt,op,backend,dtype", _parity_cases())
def test_entry_matches_loop_reference(fmt, op, backend, dtype):
    """Every non-oracle entry reproduces the loop oracle bit-for-tolerance."""
    obj = _container(fmt, dtype)
    with _x64_ctx(dtype):
        cap = R.get(fmt, op, backend).probe(obj, R.KernelContext())
        if not cap.ok:
            pytest.skip(f"({fmt}, {op}, {backend}): {cap.reason}")
        fn = R.build(obj, fmt, op, backend).fn
        out = np.asarray(fn(jnp.asarray(_operand(obj, op, dtype))))
    ref = _oracle(fmt, op, dtype)
    tol = 1e-4 if dtype == np.float32 else 1e-10
    scale = max(1e-9, float(np.abs(ref).max()))
    assert out.shape == ref.shape
    assert float(np.abs(out - ref).max()) / scale < tol


def test_parity_suite_spans_six_corpus_matrices():
    assert len(set(PARITY_MATRIX.values())) >= 6
    assert set(PARITY_MATRIX.values()) <= set(corpus.names())


# --- value-dtype x backend grid (compressed-value containers) ---------------

#: error budget per storage dtype, relative to the f64 loop oracle and the
#: oracle's max magnitude.  Rounding error for the float dtypes is
#: ~eps * sqrt(nnz/row); the quantized dtypes add the per-group scale error.
VALUE_DTYPE_TOL = {
    "f32": 1e-5, "bf16": 3e-2, "f16": 1e-2, "fp8_e4m3": 2e-1, "int8": 5e-2,
}

_VD_CONTAINERS: dict = {}


def _vd_container(fmt: str, vd: str):
    key = (fmt, vd)
    if key not in _VD_CONTAINERS:
        _VD_CONTAINERS[key] = F.with_value_dtype(_container(fmt, np.float64), vd)
    return _VD_CONTAINERS[key]


def _vd_cases():
    cases = []
    for fmt in PARITY_MATRIX:
        for vd in VALUE_DTYPE_TOL:
            for backend in ("xla", "loop_reference", "pallas_interpret"):
                if not R.has(fmt, "spmv", backend):
                    continue
                cases.append(pytest.param(fmt, vd, backend,
                                          id=f"{fmt}-{vd}-{backend}"))
    return cases


@pytest.mark.parametrize("fmt,vd,backend", _vd_cases())
def test_value_dtype_entry_matches_f64_oracle(fmt, vd, backend):
    """Every entry on a value-compressed container reproduces the f64 loop
    oracle within the dtype's error budget; unsupported (backend, dtype)
    combinations skip via their probes, never crash."""
    obj = _vd_container(fmt, vd)
    assert F.container_value_dtype(obj) == vd
    cap = R.get(fmt, "spmv", backend).probe(obj, R.KernelContext())
    if not cap.ok:
        assert cap.reason  # a probe rejection always says why
        pytest.skip(f"({fmt}, spmv, {backend}, {vd}): {cap.reason}")
    x64 = _container(fmt, np.float64)
    x = _operand(x64, "spmv", np.float32)
    out = np.asarray(R.build(obj, fmt, "spmv", backend).fn(jnp.asarray(x)))
    ref = _oracle(fmt, "spmv", np.float64)
    scale = max(1e-9, float(np.abs(ref).max()))
    assert out.shape == ref.shape
    assert float(np.abs(out - ref).max()) / scale < VALUE_DTYPE_TOL[vd]


def test_value_dtype_gate_rejects_quantized_bsr_pallas():
    """The BELL Pallas entries stream raw blocks (no per-block scale
    plumbing): their capability gate must reject quantized containers with
    the dtype named in the reason."""
    obj = _vd_container("bsr", "int8")
    cap = R.get("bsr", "spmm", "pallas_interpret").probe(obj, R.KernelContext())
    assert not cap.ok and "int8" in cap.reason
    assert R.get("bsr", "spmm", "pallas_interpret").value_dtypes == \
        R.FLOAT_PALLAS_VALUE_DTYPES


def test_registry_table_has_value_dtype_column():
    rows = R.table_rows()
    assert all("value_dtypes" in r for r in rows)
    md = R.format_table(markdown=True)
    assert "dtypes" in md.splitlines()[0]
    # the BELL restriction is visible in the published table
    assert "f32,bf16,f16" in md


# --- slab entries (the distributed executors' inner multiplies) -------------


@pytest.mark.parametrize("pack", ["ell", "sell"])
@pytest.mark.parametrize("op", ["spmv", "spmm"])
def test_slab_entries_match_loop_reference(pack, op):
    from repro.kernels.slab import SlabMeta
    rng = np.random.default_rng(7)
    rows_pp, W, n, L, k = 16, 5, 64, 160, 3
    meta = SlabMeta(pack, rows_pp)
    if pack == "ell":
        colb = jnp.asarray(rng.integers(0, n, (rows_pp, W)).astype(np.int32))
        valb = jnp.asarray(rng.standard_normal((rows_pp, W)).astype(np.float32))
        ridb = jnp.zeros((1, 1), jnp.int32)
    else:
        colb = jnp.asarray(rng.integers(0, n, (L,)).astype(np.int32))
        valb = jnp.asarray(rng.standard_normal((L,)).astype(np.float32))
        ridb = jnp.asarray(rng.integers(0, rows_pp + 1, (L,)).astype(np.int32))
    x = rng.standard_normal((n,) if op == "spmv" else (n, k)).astype(np.float32)
    out = R.build(meta, f"slab_{pack}", op, "xla").fn(colb, valb, ridb, jnp.asarray(x))
    ref = R.build(meta, f"slab_{pack}", op, "loop_reference").fn(
        colb, valb, ridb, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# --- capability probes: unsupported combos skip, never crash ----------------


def test_compiled_pallas_probes_reject_off_tpu():
    if jax.default_backend() == "tpu":
        pytest.skip("this assertion is the off-TPU half")
    for e in R.entries(backend="pallas"):
        if e.format.startswith("slab_"):
            continue
        obj = _container(e.format, np.float32) if e.format in PARITY_MATRIX else None
        cap = e.probe(obj, R.KernelContext())
        assert not cap.ok and cap.reason


def test_interpret_probes_reject_float64():
    for fmt in ("csr", "sell", "dia"):
        obj = _container(fmt, np.float64)
        cap = R.get(fmt, "spmv", "pallas_interpret").probe(obj, R.KernelContext())
        assert not cap.ok and "f64" in cap.reason
        with pytest.raises(R.BackendUnavailable):
            R.build(obj, fmt, "spmv", "pallas_interpret")


def test_sell_vmem_probe_and_plan_fallback(hh_small):
    """A chip whose VMEM fits nothing rejects the Pallas tiling; an explicit
    backend="pallas" plan degrades to the XLA formulation, not a crash."""
    sell = F.SELL.from_csr(hh_small, C=8)
    tiny = dataclasses.replace(R.KernelContext().chip, vmem_bytes=1024)
    cap = R.get("sell", "spmv", "pallas_interpret").probe(
        sell, R.KernelContext(chip=tiny))
    assert not cap.ok
    plan = SpMVPlan.compile(sell, backend="pallas", chip=tiny)
    assert plan.report.kernel == "xla"
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        hh_small.shape[1]).astype(np.float32))
    assert plan(x).shape == (hh_small.shape[0],)


def test_sell_pallas_spmm_wide_batch_degrades_to_xla(hh_small):
    """The SpMM probe claims VMEM at k=1; at call time the build re-claims
    for the actual batch width and degrades to the fused XLA formulation
    instead of emitting a kernel whose working set cannot fit."""
    sell = F.SELL.from_csr(hh_small, C=8)
    # budget sized so k=1 fits but k=64 cannot: its limit sits halfway
    # between the two claims
    from repro.kernels.sell import sell_autotune
    from repro.kernels.sell_spmv import vmem_bytes
    from repro.utils import hw
    base = sell_autotune(sell, R.KernelContext())
    wide = vmem_bytes(base.chunk_block, base.width_block, sell.C,
                      sell.shape[1], k=64)
    budget = hw.vmem_limit((base.vmem_bytes + wide) // 2)
    snug = dataclasses.replace(
        R.KernelContext().chip,
        vmem_bytes=int(budget / hw.VMEM_BUDGET_FRACTION) + 4)
    ctx = R.KernelContext(chip=snug)
    assert R.get("sell", "spmm", "pallas_interpret").probe(sell, ctx).ok
    fn = R.build(sell, "sell", "spmm", "pallas_interpret", ctx).fn
    X = jnp.asarray(np.random.default_rng(0).standard_normal(
        (hh_small.shape[1], 64)).astype(np.float32))
    Y = np.asarray(fn(X))  # wide batch: falls back, still correct
    from repro.core import spmv as S
    np.testing.assert_allclose(Y, np.asarray(S.spmm(hh_small, X)),
                               rtol=2e-4, atol=2e-4)


def test_select_backend_memo_keyed_on_tiling_overrides(hh_small):
    """A choice memoized for one tiling override must not answer for
    another — probes depend on the re-claimed VMEM of the override."""
    sell = F.SELL.from_csr(hh_small, C=16)
    be_plain, _ = R.select_backend(sell, "sell", "spmv", R.KernelContext())
    ctx_wb = R.KernelContext(width_block=4)
    be_wb, _ = R.select_backend(sell, "sell", "spmv", ctx_wb)
    memo = getattr(sell, "_backend_choices")
    assert len(memo) == 2  # distinct keys, no cross-answer
    assert be_plain and be_wb


def test_empty_dia_probe_rejected_not_crashed():
    empty = F.DIA(np.zeros(0, np.int32), np.zeros((0, 8), np.float32), (8, 8))
    cap = R.get("dia", "spmv", "pallas_interpret").probe(empty, R.KernelContext())
    assert not cap.ok and "empty" in cap.reason
    # the XLA entry still serves it (zeros), and auto never crashes
    y = R.build(empty, "dia", "spmv", "xla").fn(jnp.ones(8, jnp.float32))
    assert np.asarray(y).shape == (8,)
    be, costs = R.select_backend(empty, "dia", "spmv")
    assert be in costs and costs


def test_unknown_entry_is_keyerror():
    with pytest.raises(KeyError, match="registered backends"):
        R.get("sell", "spmv", "nope")
    with pytest.raises(KeyError):
        R.get("ell", "spmv", "pallas")  # ELL has no Pallas kernel


def test_select_backend_memoizes_on_container(hh_small):
    sell = F.SELL.from_csr(hh_small, C=8)
    be1, costs1 = R.select_backend(sell, "sell", "spmv")
    be2, costs2 = R.select_backend(sell, "sell", "spmv")
    assert be1 == be2 and costs1 is costs2           # memo hit, same object
    assert getattr(sell, "_backend_choices")
    expected = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert be1 == expected


# --- backend="auto" across the whole corpus (acceptance criterion) ----------


@pytest.mark.parametrize("name", corpus.names())
def test_backend_auto_valid_for_corpus(name):
    m = corpus.build(name)
    plan = SpMVPlan.compile(m, format="auto", backend="auto")
    assert plan.report.kernel in ("xla", "pallas", "pallas-interpret")
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        m.shape[1]).astype(np.asarray(m.val).dtype))
    y = np.asarray(plan(x))
    assert y.shape == (m.shape[0],) and np.isfinite(y).all()


# --- the CLI table (the CI kernel-matrix step) ------------------------------


def test_registry_table_lists_every_entry():
    rows = R.table_rows()
    keys = {(r["format"], r["op"], r["backend"]) for r in rows}
    assert len(keys) == len(rows) == len(R.entries())
    # every parity-able format exposes an xla and a loop_reference oracle
    # for both ops — the invariant the parity suite stands on
    for fmt in PARITY_MATRIX:
        for op in ("spmv", "spmm"):
            assert R.has(fmt, op, "xla")
            assert R.has(fmt, op, "loop_reference")
    md = R.format_table(markdown=True)
    assert md.startswith("|") and "sell" in md and "pallas_interpret" in md


def test_new_pallas_kernels_registered():
    """PR 5's two new kernels exist as registry entries."""
    assert R.has("sell", "spmm", "pallas") and R.has("sell", "spmm", "pallas_interpret")
    assert R.has("csr", "spmv", "pallas") and R.has("csr", "spmv", "pallas_interpret")
