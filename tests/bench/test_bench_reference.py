"""The copied host references against numpy.linalg.eigvalsh."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference  # noqa: E402


def _sym(n, seed):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)) * (rng.random((n, n)) < 0.3)
    a = a + a.T + np.diag(rng.standard_normal(n) + 4)
    return a


def _csr(a):
    import scipy.sparse as sp
    c = sp.csr_matrix(a)
    return reference.HostCSR(c.indptr, c.indices, c.data, a.shape)


def test_host_csr_matvec_is_the_dense_product():
    a = _sym(40, 0)
    h = _csr(a)
    x = np.random.default_rng(1).standard_normal(40)
    assert np.allclose(h.matvec(x), a @ x, rtol=1e-13, atol=1e-13)
    assert h.nnz == np.count_nonzero(a)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lanczos_with_reorthogonalisation_finds_the_whole_spectrum(seed):
    a = _sym(30, seed)
    h = _csr(a)
    v0 = np.random.default_rng(seed).standard_normal(30)
    theta = reference.lanczos(h.matvec, v0, 30, reorthogonalize=True)
    assert np.allclose(theta, np.linalg.eigvalsh(a), atol=1e-9)


@pytest.mark.parametrize("seed", [3, 4])
def test_plain_recurrence_finds_the_extreme_eigenvalues(seed):
    a = _sym(60, seed)
    h = _csr(a)
    v0 = np.random.default_rng(seed).standard_normal(60)
    theta = reference.lanczos(h.matvec, v0, 60, reorthogonalize=False)
    ev = np.linalg.eigvalsh(a)
    assert reference.ritz_gap(theta, ev) < 1e-8


def test_bf16_rounding_makes_the_product_coarser_than_the_float64_reference():
    a = _sym(200, 5)
    h = _csr(a)
    x = np.random.default_rng(6).standard_normal(200)
    e16 = reference.rel_err(reference.to_bf16(a) @ reference.to_bf16(x), h.matvec(x))
    assert 1e-4 < e16 < 3e-2
    assert reference.to_bf16(np.float32(1.0 + 2 ** -9)) == np.float32(1.0)


def test_ritz_gap_is_scaled_by_the_spectrum():
    assert reference.ritz_gap([-2.0, 10.0], [-2.0, 10.0]) == 0.0
    assert reference.ritz_gap([-2.1, 10.0], [-2.0, 10.0]) == pytest.approx(0.01)
