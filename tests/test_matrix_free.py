"""Matrix-free operator suite: detection, kernel parity, and guards.

The contract under test (core/formats.MatrixFreeOperator +
kernels/matrix_free.py + the perfmodel/plan/tunedb wiring):

* detection — ``detect_matrix_free`` recovers a descriptor whose
  ``materialize()`` is *bitwise* identical to the source CSR, and returns
  None for matrices without per-diagonal structure (powerlaw, random);
* parity — every registered ``(matrix_free, op, backend)`` entry matches
  the materialized-CSR ``loop_reference`` oracle over the eligible corpus
  × {spmv, spmm} × {f32, f64}, boundary rows included.  The xla and loop
  entries must be bitwise-equal (same ascending-column accumulation
  order); Pallas entries get the usual backend derates;
* guards — structural converters (ELL/JDS/SELL/DIA/split_dia) reject the
  descriptor with a TypeError naming ``materialize`` as the escape hatch;
* selection — ``format="auto"`` picks matrix_free only where eligible and
  never moves the pick for non-eligible matrices (the golden pins in
  test_tunedb.py cover the full-corpus identity).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import corpus
from repro.core import formats as F
from repro.core import perfmodel as PM
from repro.core import tunedb as TDB
from repro.core.plan import SpMVPlan
from repro.core.planconfig import PlanConfig
from repro.kernels import registry as R

ELIGIBLE = tuple(corpus.matrix_free_names())
NOT_ELIGIBLE = ("powerlaw", "random_uniform", "blocksparse")
DTYPES = (np.float32, np.float64)
MF_BACKENDS = ("xla", "loop_reference", "pallas", "pallas_interpret")
#: bitwise-equal backends: same ascending-offset (= ascending-column)
#: accumulation as the CSR row-major loop oracle
EXACT_BACKENDS = ("xla", "loop_reference")

_CSR_CACHE: dict = {}
_OP_CACHE: dict = {}


def _x64_ctx(dtype):
    if dtype == np.float64:
        return jax.enable_x64(True)
    import contextlib
    return contextlib.nullcontext()


def _csr(name: str, dtype) -> F.CSR:
    key = (name, np.dtype(dtype).name)
    if key not in _CSR_CACHE:
        src = corpus.build(name)
        _CSR_CACHE[key] = F.CSR(np.asarray(src.row_ptr), np.asarray(src.col_idx),
                                np.asarray(src.val).astype(dtype), src.shape)
    return _CSR_CACHE[key]


def _mf(name: str, dtype) -> F.MatrixFreeOperator:
    key = (name, np.dtype(dtype).name)
    if key not in _OP_CACHE:
        op = F.detect_matrix_free(_csr(name, dtype))
        assert op is not None, f"{name} flagged eligible but did not detect"
        _OP_CACHE[key] = op
    return _OP_CACHE[key]


def _operand(n: int, op: str, dtype, k: int = 3):
    rng = np.random.default_rng(7)
    shape = (n,) if op == "spmv" else (n, k)
    return jnp.asarray(rng.standard_normal(shape).astype(dtype))


def _oracle(name: str, op: str, dtype, x):
    m = _csr(name, dtype)
    kern = R.build(m, "csr", op, "loop_reference")
    return np.asarray(kern.fn(x))


# ---------------------------------------------------------------------------
# detection + materialization round-trip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ELIGIBLE)
def test_detect_materialize_bitwise_round_trip(name):
    m = _csr(name, np.float64)
    op = _mf(name, np.float64)
    back = F.materialize(op)
    assert back.shape == m.shape
    np.testing.assert_array_equal(np.asarray(back.row_ptr), np.asarray(m.row_ptr))
    np.testing.assert_array_equal(np.asarray(back.col_idx), np.asarray(m.col_idx))
    np.testing.assert_array_equal(np.asarray(back.val), np.asarray(m.val))
    assert op.nnz == m.nnz
    # the point of the format: zero index arrays in the container
    leaves = jax.tree_util.tree_leaves(op)
    assert all(np.issubdtype(np.asarray(l).dtype, np.floating) for l in leaves)


@pytest.mark.parametrize("name", NOT_ELIGIBLE)
def test_detect_returns_none_for_unstructured(name):
    assert F.detect_matrix_free(corpus.build(name)) is None


def test_detection_is_cached_on_the_container():
    m = corpus.build("laplace2d")
    assert F.detect_matrix_free(m) is F.detect_matrix_free(m)


def test_corpus_accessors():
    assert set(ELIGIBLE) == {n for n in corpus.names()
                             if corpus.get(n).matrix_free}
    op = corpus.matrix_free_operator("laplace3d")
    assert isinstance(op, F.MatrixFreeOperator)
    with pytest.raises(ValueError, match="not matrix-free-eligible"):
        corpus.matrix_free_operator("powerlaw")
    assert corpus.stats("laplace3d")["matrix_free_eligible"] is True
    assert corpus.stats("powerlaw")["matrix_free_eligible"] is False


# ---------------------------------------------------------------------------
# kernel parity: every backend vs the materialized-CSR loop oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "f64"))
@pytest.mark.parametrize("op_name", ("spmv", "spmm"))
@pytest.mark.parametrize("name", ELIGIBLE)
def test_parity_vs_materialized_oracle(name, op_name, dtype):
    with _x64_ctx(dtype):
        mf = _mf(name, dtype)
        x = _operand(mf.shape[1], op_name, dtype)
        ref = _oracle(name, op_name, dtype, x)
        caps = R.capabilities(mf, "matrix_free", op_name)
        ran = []
        for backend in MF_BACKENDS:
            if not caps[backend].ok:
                continue
            y = np.asarray(R.build(mf, "matrix_free", op_name, backend).fn(x))
            scale = max(1e-30, float(np.max(np.abs(ref))))
            err = float(np.max(np.abs(y - ref))) / scale
            if backend in EXACT_BACKENDS:
                np.testing.assert_array_equal(
                    y, ref, err_msg=f"{backend} not bitwise vs CSR loop")
            else:
                tol = 1e-4 if dtype == np.float32 else 1e-10
                assert err <= tol, f"{backend}: {err:.3e} > {tol}"
            ran.append(backend)
        assert "xla" in ran and "loop_reference" in ran


@pytest.mark.parametrize("name", ELIGIBLE)
def test_boundary_rows_masked(name):
    """First/last rows clip off-matrix diagonal elements; a basis vector at
    column 0 must only excite rows whose diagonals genuinely reach it."""
    mf = _mf(name, np.float64)
    dense = _csr(name, np.float64).to_dense()
    with _x64_ctx(np.float64):
        for col in (0, mf.shape[1] - 1):
            e = np.zeros(mf.shape[1])
            e[col] = 1.0
            y = np.asarray(R.build(mf, "matrix_free", "spmv", "xla").fn(
                jnp.asarray(e)))
            np.testing.assert_array_equal(y, np.asarray(dense)[:, col])


@pytest.mark.parametrize("tile", [None, 4096], ids=("autotuned", "t4096"))
def test_pallas_interpret_multi_tile(tile):
    """The corpus operators fit one row tile; a 9600-row 3-D Laplacian
    runs the kernel over 2-3 tiles, so the periodic row rules and shifted
    windows are checked at program_id > 0 and across tile edges."""
    from repro.core.matrices import laplacian_3d

    m = laplacian_3d(24, 20, 20, dtype=np.float32)
    mf = F.detect_matrix_free(m)
    ctx = R.KernelContext(tile=tile)
    dense = np.asarray(m.to_dense(), np.float64)
    for op_name in ("spmv", "spmm"):
        ck = R.build(mf, "matrix_free", op_name, "pallas_interpret", ctx)
        assert m.shape[0] > ck.choice, ck.choice   # at least two tiles
        x = _operand(m.shape[1], op_name, np.float32)
        ref = dense @ np.asarray(x, np.float64)
        np.testing.assert_allclose(np.asarray(ck.fn(x)), ref,
                                   rtol=1e-4, atol=1e-4, err_msg=op_name)


def test_f64_rejected_by_pallas_probes():
    mf = _mf("laplace2d", np.float64)
    caps = R.capabilities(mf, "matrix_free", "spmv")
    assert not caps["pallas_interpret"].ok
    assert not caps["pallas"].ok


# ---------------------------------------------------------------------------
# structural-converter guards + the materialize escape hatch
# ---------------------------------------------------------------------------


def test_converters_reject_descriptor():
    op = _mf("banded_narrow", np.float32)
    for conv in (F.ELL.from_csr, F.JDS.from_csr, F.SELL.from_csr,
                 F.DIA.from_csr, F.split_dia):
        with pytest.raises(TypeError, match="materialize"):
            conv(op)
    with pytest.raises(TypeError, match="materialize"):
        F.convert(op, "ell")
    # identity conversion is fine; the escape hatch gives a real CSR
    assert F.convert(op, "matrix_free") is op
    assert isinstance(F.ELL.from_csr(F.materialize(op)), F.ELL)


def test_materialize_rejects_non_descriptor():
    with pytest.raises(TypeError):
        F.materialize(corpus.build("laplace2d"))


def test_with_value_dtype_casts_and_rejects_quantized():
    op = _mf("holstein_exact", np.float32)
    if op.data is not None:
        cast = F.with_value_dtype(op, "bf16")
        assert cast.value_dtype == "bf16"
        assert F.container_value_dtype(cast) == "bf16"
    with pytest.raises(TypeError):
        F.with_value_dtype(op, "int8")


# ---------------------------------------------------------------------------
# selection, plan compile, and cost model
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ELIGIBLE)
def test_plan_compiles_and_auto_picks_matrix_free(name):
    m = F.with_value_dtype(corpus.build(name), "f32")
    x = _operand(m.shape[1], "spmv", np.float32)
    ref = np.asarray(R.build(m, "csr", "spmv", "loop_reference").fn(x))
    plan = SpMVPlan.compile(m, PlanConfig(format="matrix_free"))
    assert plan.report.format == "matrix_free"
    np.testing.assert_allclose(np.asarray(plan(x)), ref, rtol=2e-6, atol=1e-6)
    auto = SpMVPlan.compile(m, PlanConfig(format="auto"))
    assert auto.report.format == "matrix_free"


def test_auto_never_picks_matrix_free_when_ineligible():
    for name in NOT_ELIGIBLE:
        m = F.with_value_dtype(corpus.build(name), "f32")
        plan = SpMVPlan.compile(m, PlanConfig(format="auto"))
        assert plan.report.format != "matrix_free"


def test_streamed_bytes_drop_index_traffic():
    name = "laplace3d"
    csr = _csr(name, np.float32)
    op = _mf(name, np.float32)
    full = PM.spmv_streamed_bytes(csr)
    no_idx = PM.spmv_streamed_bytes(csr, generated_indices=True)
    mf_bytes = PM.spmv_streamed_bytes(op)
    assert no_idx < full  # the counterfactual really zeroes index bytes
    # a fully-generated descriptor streams only x + y (+ stored lanes)
    assert mf_bytes < no_idx
    assert mf_bytes == PM.spmv_streamed_bytes(op, generated_indices=True)
    assert PM.matrix_stream_bytes(op) == 4.0 * op.n_stored * op.shape[0]


def test_select_format_reports_matrix_free_balance():
    m = corpus.build("banded_wide")
    choice = PM.select_format(m)
    assert choice.format == "matrix_free"
    preds = choice.predicted_time_s
    assert preds["matrix_free"] > 0
    # it won against at least one materialized diagonal candidate
    assert any(preds["matrix_free"] < preds[f] for f in preds if f != "matrix_free")


# ---------------------------------------------------------------------------
# tunedb signature + serving composition
# ---------------------------------------------------------------------------


def test_tunedb_signs_the_descriptor():
    a = F.detect_matrix_free(corpus.build("laplace2d"))
    b = F.detect_matrix_free(corpus.build("laplace3d"))
    sig_a, sig_b = TDB.signature_of(a), TDB.signature_of(b)
    assert sig_a and sig_b and sig_a != sig_b
    assert len(sig_a) == 16 and int(sig_a, 16) >= 0
    # independent detections of the same pattern share a signature
    fresh = F.MatrixFreeOperator.from_csr(corpus.build("laplace2d"))
    assert TDB.signature_of(fresh) == sig_a
    # stored-lane payload participates: casting values re-signs
    hh = F.detect_matrix_free(corpus.build("holstein_exact"))
    if hh.data is not None:
        assert TDB.signature_of(F.with_value_dtype(hh, "bf16")) != \
            TDB.signature_of(hh)


def test_server_and_eigensolver_compose():
    from repro.core.eigensolver import lanczos
    from repro.serve.engine import BatchingSpMVServer
    m = F.with_value_dtype(corpus.build("laplace2d"), "f32")
    srv = BatchingSpMVServer()
    rep = srv.register("lap", m, config=PlanConfig(format="matrix_free"))
    assert rep.format == "matrix_free"
    x = _operand(m.shape[1], "spmv", np.float32)
    np.testing.assert_allclose(
        np.asarray(srv.spmv("lap", x)),
        np.asarray(R.build(m, "csr", "spmv", "loop_reference").fn(x)),
        rtol=2e-6, atol=1e-6)
    plan = SpMVPlan.compile(m, PlanConfig(format="matrix_free"))
    res = lanczos(plan.spmv, m.shape[0], m=20, dtype=np.float32)
    assert np.isfinite(float(res.eigenvalues[0]))
    assert res.n_spmv == 20


# ---------------------------------------------------------------------------
# registry CLI table
# ---------------------------------------------------------------------------


def test_registry_table_lists_matrix_free_with_hooks():
    md = R.format_table(markdown=True)
    head = md.splitlines()[0]
    for col in ("cost", "autotune"):
        assert col in head
    rows = [l for l in md.splitlines() if l.startswith("| matrix_free")]
    assert len(rows) == len(R.entries("matrix_free"))
    assert any("matrix_free_autotune" in r for r in rows)
