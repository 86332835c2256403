"""Compulsory bytes come from the operator, never from a plan's format."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import compulsory  # noqa: E402


def test_hand_counts_on_a_tiny_matrix():
    # 3x3 with 5 stored float32 values: 5*4 value bytes + (3 + 3)*4 for x and y
    assert compulsory.spmv_bytes(3, 3, 5, "float32", True) == 20 + 24
    # generated values: only x and y
    assert compulsory.spmv_bytes(3, 3, 5, "float32", False) == 24
    assert compulsory.spmv_bytes(4, 3, 5, "float64", True) == 40 + 56
    # symmetric, 3 of the 5 on the diagonal: the diagonal and one of each pair
    assert compulsory.spmv_bytes(3, 3, 5, "float32", True, n_diag=3) == 4 * 4 + 24


def test_the_paper_cells():
    hh = compulsory.spmv_bytes(1_201_200, 1_201_200, 16_760_654, "float32", True)
    assert hh == 4 * 16_760_654 + 8 * 1_201_200           # ~77 MB
    # stated symmetric, with its full main diagonal: ~45.5 MB
    sym = compulsory.spmv_bytes(1_201_200, 1_201_200, 16_760_654, "float32", True,
                                n_diag=1_201_200)
    assert sym == 4 * (16_760_654 + 1_201_200) // 2 + 8 * 1_201_200 == 45_533_308


def test_the_holstein_operator_counts_one_triangle():
    sys.path.insert(0, str(ROOT / "src"))
    from bench import common
    cell = common.resolve("hh_lanczos", ROOT)
    cfg = dict(cell.config, n=3_000)
    cfg.pop("sha256")
    op = common.operator_module(cell).build(cfg, 2**33 + 5)
    a = op.host.a64
    assert cfg["symmetric"] and abs(a - a.T).max() == 0
    assert op.n_diag == np.count_nonzero(a.diagonal()) == op.n
    lower = a.nnz - (a.nnz - op.n_diag) // 2          # the diagonal and one triangle
    assert compulsory.spmv_bytes(op.n, op.n, op.nnz, op.dtype, True, op.n_diag) == (
        4 * lower + 8 * op.n)
    no_claim = common.operator_module(cell).build(dict(cfg, symmetric=False), 7)
    assert no_claim.n_diag is None


@pytest.mark.parametrize("fmt", ["ell", "sell", "dia", "jds"])
def test_two_formats_of_one_operator_read_the_same_bytes(fmt):
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.formats import convert
    from repro.core.matrices import laplacian_2d
    m = laplacian_2d(6, 5, dtype=np.float32)
    other = convert(m, fmt)
    def count(container):
        dense = np.asarray(container.to_dense())
        return dense.shape[0], dense.shape[1], int(np.count_nonzero(dense))
    assert count(m) == count(other)
    a = compulsory.spmv_bytes(*count(m), "float32", True)
    b = compulsory.spmv_bytes(*count(other), "float32", True)
    assert a == b == 4 * m.nnz + 4 * 60
