"""GAP's PageRank recurrence on the host in float64, with no JAX: the plain
reference that the ``pagerank`` runner's check compares the program with.

The operator is built here from the graph's pattern alone: ``P = A D^-1``
with ``1 / deg(j)`` at ``(i, j)`` in float64, ``deg(j)`` the stored
entries of column ``j``.  From ``x_0 = t`` (the teleport vector), each
iteration computes ``x' = (1 - d) t + d P x`` and the L1 change
``||x' - x||_1``, and stops when the change falls below ``tol`` or after
``max_iters`` iterations.
"""
from __future__ import annotations

import numpy as np

from bench.reference import HostCSR


def transition(row_ptr, col_idx, shape) -> HostCSR:
    """The column-stochastic transition of the adjacency pattern
    ``(row_ptr, col_idx)``, its values ``1 / deg(j)`` in float64."""
    col = np.asarray(col_idx, np.int64)
    deg = np.bincount(col, minlength=shape[1]).astype(np.float64)
    return HostCSR(row_ptr, col, 1.0 / deg[col], shape)


def pagerank(matvec, teleport, damping: float, tol: float, max_iters: int):
    """Returns ``(scores, iterations, l1_changes)``."""
    t = np.asarray(teleport, np.float64)
    x, changes = t, []
    for _ in range(max_iters):
        x_new = (1.0 - damping) * t + damping * np.asarray(matvec(x), np.float64)
        changes.append(float(np.abs(x_new - x).sum()))
        x = x_new
        if changes[-1] < tol:
            break
    return x, len(changes), np.asarray(changes)


def score_l1_err(x, ref) -> float:
    """``||x - ref||_1 / ||ref||_1``."""
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.abs(x - ref).sum() / max(1e-300, np.abs(ref).sum()))
