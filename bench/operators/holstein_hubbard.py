"""The paper's Holstein-Hubbard surrogate with values drawn from the seed.

The pattern (and the base values) are the program's generator's at the
configuration's ``pattern_seed``, pinned by a sha256, so every run hits the
same compiled programs.  ``--seed`` redraws the values symmetrically on that
pattern: each stored value is multiplied by ``0.5 + u``, where ``u`` in
[0, 1) is a hash of (seed, min(i, j), max(i, j)); the draw keeps each
value's sign and scale, and the symmetry that Lanczos needs.
"""
from __future__ import annotations

import time

import numpy as np

from bench.common import Operator, check_fingerprint
from bench.reference import HostCSR

_M1 = np.uint64(0x9E3779B97F4A7C15)
_M2 = np.uint64(0xBF58476D1CE4E5B9)
_M3 = np.uint64(0x94D049BB133111EB)


def _mix(z: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser, elementwise on uint64."""
    z = (z ^ (z >> np.uint64(30))) * _M2
    z = (z ^ (z >> np.uint64(27))) * _M3
    return z ^ (z >> np.uint64(31))


def symmetric_uniform(seed: int, rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """u in [0, 1) as a function of (seed, min(i, j), max(i, j))."""
    lo = np.minimum(rows, cols).astype(np.uint64)
    hi = np.maximum(rows, cols).astype(np.uint64)
    s = _mix(np.full(1, seed % (1 << 64), np.uint64) + _M1)[0]
    with np.errstate(over="ignore"):
        z = _mix(_mix(lo * _M1 + s) ^ (hi * _M3 + _M2))
    return (z >> np.uint64(11)).astype(np.float64) * (1.0 / (1 << 53))


def build(config: dict, seed: int) -> Operator:
    from repro.core.formats import CSR
    from repro.core.matrices import holstein_hubbard_surrogate

    g = config["generator"]
    t0 = time.perf_counter()
    m = holstein_hubbard_surrogate(
        config["n"], nnz_per_row=g["nnz_per_row"],
        n_secondary_diags=g["n_secondary_diags"], frac_in_diags=g["frac_in_diags"],
        band_frac=g["band_frac"], seed=g["pattern_seed"], dtype=np.float32)
    build_s = time.perf_counter() - t0
    rp = np.array(m.row_ptr, np.int32)
    col = np.array(m.col_idx, np.int32)
    base = np.array(m.val, np.float32)
    check_fingerprint(config, rp, col, base)
    rows = np.repeat(np.arange(m.shape[0], dtype=np.int64), np.diff(rp))
    with np.errstate(over="ignore"):
        val = (base.astype(np.float64)
               * (0.5 + symmetric_uniform(seed, rows, col))).astype(np.float32)
    matrix = CSR(rp.copy(), col.copy(), val.copy(), tuple(m.shape))
    host = HostCSR(rp, col, val, m.shape)
    n_diag = int(np.count_nonzero(rows == col)) if config.get("symmetric") else None
    return Operator(config["name"], m.shape[0], host.nnz, config["dtype"], True,
                    matrix, host, build_s, n_diag)
