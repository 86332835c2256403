"""Reference SpMV per format vs the dense oracle.

Hypothesis property sweeps live in test_property.py (optional test extra).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats as F
from repro.core import spmv as S
from repro.core.matrices import block_sparse_dense, laplacian_2d

FORMATS = [("csr", {}), ("ell", {}), ("jds", {}), ("sell", dict(C=8)),
           ("sell", dict(C=16, sigma=32, sort_cols=True)), ("hybrid", {})]


def _check(m, fmt, kw, dtype=np.float32, rtol=2e-5):
    d = m.to_dense().astype(np.float64)
    x = np.random.default_rng(3).standard_normal(m.shape[1]).astype(dtype)
    y_ref = d @ x.astype(np.float64)
    obj = F.convert(m, fmt, **kw)
    y = np.asarray(S.spmv(obj, jnp.asarray(x)), np.float64)
    scale = max(1e-9, np.abs(y_ref).max())
    assert np.abs(y - y_ref).max() / scale < rtol, fmt


@pytest.mark.parametrize("fmt,kw", FORMATS)
def test_formats_vs_dense(hh_small, fmt, kw):
    _check(hh_small, fmt, kw)


@pytest.mark.parametrize("fmt,kw", FORMATS)
def test_laplacian(fmt, kw):
    _check(laplacian_2d(16, 12, dtype=np.float32), fmt, kw)


def test_bsr_spmv_spmm():
    d = block_sparse_dense(64, 256, (8, 128), 0.4, seed=1)
    m = F.BSR.from_dense(d, (8, 128))
    x = np.random.default_rng(0).standard_normal(256).astype(np.float32)
    y = np.asarray(S.bsr_spmv(m, jnp.asarray(x)))
    np.testing.assert_allclose(y, d @ x, rtol=2e-4, atol=1e-4)
    X = np.random.default_rng(1).standard_normal((256, 16)).astype(np.float32)
    Y = np.asarray(S.bsr_spmm(m, jnp.asarray(X)))
    np.testing.assert_allclose(Y, d @ X, rtol=2e-4, atol=1e-4)


def test_make_spmv_jitted(hh_small):
    f = S.make_spmv(F.convert(hh_small, "sell", C=8))
    x = jnp.asarray(np.random.default_rng(0).standard_normal(hh_small.shape[1]).astype(np.float32))
    y1 = f(x)
    y2 = f(x * 2)
    np.testing.assert_allclose(np.asarray(y2), 2 * np.asarray(y1), rtol=1e-5)


def test_hybrid_facade_traced_then_eager(hh_small):
    """The facade's hybrid dispatch is the registry's XLA entry; building
    it inside a trace first must leave no tracer in the device-copy store
    for the eager and plan calls that follow."""
    from repro.core.plan import SpMVPlan

    m = F.convert(hh_small, "hybrid")
    n = hh_small.shape[1]
    X = jnp.asarray(np.random.default_rng(0).standard_normal((n, 3)).astype(np.float32))
    d = np.asarray(m.to_dense())
    y = np.asarray(S.make_spmv(m)(X[:, 0]))
    np.testing.assert_allclose(y, d @ np.asarray(X[:, 0]), rtol=2e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(S.spmm(m, X)), d @ np.asarray(X),
                               rtol=2e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(SpMVPlan.compile(m)(X[:, 0])), y,
                               rtol=1e-5, atol=1e-5)


def test_flops_accounting(hh_small):
    assert S.flops_of(hh_small) == 2 * hh_small.nnz


def test_row_ids_cached_no_recompute():
    """csr_row_ids / bsr_block_row_ids build once per container, ever."""
    from repro.core.matrices import holstein_hubbard_surrogate
    m = holstein_hubbard_surrogate(300, seed=11)
    before = S.precompute_stats()
    ids1 = S.csr_row_ids(m)
    x = jnp.asarray(np.ones(300, np.float32))
    f = S.make_spmv(m)
    for _ in range(3):
        f(x)
        S.spmv(m, x)
    ids2 = S.csr_row_ids(m)
    assert ids1 is ids2
    assert S.precompute_stats()["csr_row_ids"] - before["csr_row_ids"] == 1

    d = block_sparse_dense(32, 256, (8, 128), 0.5, seed=4)
    mb = F.BSR.from_dense(d, (8, 128))
    before = S.precompute_stats()
    xb = jnp.asarray(np.ones(256, np.float32))
    for _ in range(3):
        S.bsr_spmv(mb, xb)
    assert S.precompute_stats()["bsr_block_row_ids"] - before["bsr_block_row_ids"] == 1


def test_naive_matches_vectorized(hh_small):
    """The legacy formulations (benchmark baseline) agree with the new
    vectorized dispatch for every format that has both."""
    x = jnp.asarray(np.random.default_rng(5).standard_normal(hh_small.shape[1]).astype(np.float32))
    for fmt, kw in [("csr", {}), ("jds", {}), ("sell", dict(C=8)), ("hybrid", {})]:
        obj = F.convert(hh_small, fmt, **kw)
        np.testing.assert_allclose(np.asarray(S.naive_spmv(obj, x)),
                                   np.asarray(S.spmv(obj, x)), rtol=2e-5, atol=2e-5)


def test_empty_rows():
    # rows with zero entries must produce zeros, not garbage
    rows = np.array([0, 0, 3], np.int32)
    cols = np.array([1, 2, 0], np.int32)
    vals = np.array([1.0, 2.0, 3.0], np.float32)
    m = F.CSR.from_coo(F.COO(rows, cols, vals, (5, 4)))
    x = jnp.asarray(np.ones(4, np.float32))
    for fmt, kw in FORMATS:
        y = np.asarray(S.spmv(F.convert(m, fmt, **kw), x))
        np.testing.assert_allclose(y, m.to_dense() @ np.ones(4), atol=1e-6)
