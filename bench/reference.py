"""Plain host references, independent of the code under test.

``HostCSR`` multiplies with SciPy's CSR matrix in float64; ``lanczos`` is
the three-term recurrence of the program's solver, with or without full
reorthogonalisation, run on the host in the precision it is given.
``to_bf16`` is the rounding of the control (``bench/control.py``), the same
product one precision step down.
"""
from __future__ import annotations

import numpy as np


def to_bf16(a) -> np.ndarray:
    """Round to bfloat16 (nearest even) and widen back to float32."""
    import ml_dtypes
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


class HostCSR:
    """The operator as plain CSR arrays on the host."""

    def __init__(self, row_ptr, col, val, shape):
        import scipy.sparse as sp
        self.shape = tuple(shape)
        self.n = self.shape[0]
        rp = np.asarray(row_ptr, np.int64)
        col = np.asarray(col, np.int64)
        self.nnz = int(rp[-1])
        self.a64 = sp.csr_matrix((np.asarray(val, np.float64), col, rp), shape=self.shape)

    def matvec(self, x) -> np.ndarray:
        return self.a64 @ np.asarray(x, np.float64)


def rel_err(y, ref) -> float:
    """max |y - ref| / max |ref|."""
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.abs(y - ref).max() / max(1e-300, np.abs(ref).max()))


def lanczos(matvec, v0, steps: int, *, reorthogonalize: bool = False,
            dtype=np.float64) -> np.ndarray:
    """The recurrence of ``core.eigensolver.lanczos`` on the host; returns
    the Ritz values (ascending).  With ``reorthogonalize`` the basis is
    Gram-Schmidt corrected twice per step, as the program does."""
    v = np.asarray(v0, dtype)
    v = v / np.linalg.norm(v)
    basis = [v] if reorthogonalize else None
    alphas, betas = [], []
    beta, v_prev = 0.0, np.zeros_like(v)
    for _ in range(steps):
        w = np.asarray(matvec(v), dtype)
        alpha = float(v @ w)
        w = w - dtype(alpha) * v - dtype(beta) * v_prev
        if reorthogonalize:
            B = np.stack(basis)
            w = w - B.T @ (B @ w)
            w = w - B.T @ (B @ w)
        beta_new = float(np.linalg.norm(w))
        alphas.append(alpha)
        betas.append(beta_new)
        if not (np.isfinite(alpha) and np.isfinite(beta_new)):
            raise FloatingPointError("the host recurrence broke down")
        if beta_new < 1e-12 * max(1.0, abs(alpha)):
            break
        v_prev = v
        v = w / dtype(beta_new)
        if reorthogonalize:
            basis.append(v)
        beta = beta_new
    a = np.asarray(alphas)
    b = np.asarray(betas[: len(alphas) - 1])
    T = np.diag(a) + np.diag(b, 1) + np.diag(b, -1)
    return np.linalg.eigvalsh(T)


def ritz_gap(theta, theta_ref) -> float:
    """The larger gap of the two extreme Ritz values, over the spectral
    scale of the reference (its largest Ritz value in magnitude)."""
    theta = np.asarray(theta, np.float64)
    theta_ref = np.asarray(theta_ref, np.float64)
    scale = max(abs(theta_ref[0]), abs(theta_ref[-1]), 1e-300)
    return float(max(abs(theta[0] - theta_ref[0]),
                     abs(theta[-1] - theta_ref[-1])) / scale)
