"""GAP's PageRank operator on a Graph500 Kronecker graph.

The graph is the program's generator (``repro.core.matrices.kronecker_graph``)
at the configuration's scale, edge factor, initiator and ``pattern_seed``;
the operator is its column-stochastic transition ``P = A D^-1``
(``pagerank_transition``), pinned by a sha256 over P's CSR arrays, so every
run hits the same compiled programs.  The host copy that the check uses is
built by the bench from the pattern alone, its ``1 / deg`` in float64
(``bench/pagerank_reference.transition``), so the check also sees how the
program built P.  ``--seed`` does not touch the operator: it draws the
teleport vector of each solve (``bench/runners/pagerank.py``).
"""
from __future__ import annotations

import time

import numpy as np

from bench.common import Operator, check_fingerprint
from bench.pagerank_reference import transition


def build(config: dict, seed: int) -> Operator:
    from repro.core.formats import CSR
    from repro.core.matrices import kronecker_graph, pagerank_transition

    t0 = time.perf_counter()
    adj = kronecker_graph(config["scale"], edge_factor=config["edge_factor"],
                          initiator=tuple(config["initiator"]),
                          seed=config["pattern_seed"])
    m = pagerank_transition(adj, dtype=np.dtype(config["dtype"]))
    build_s = time.perf_counter() - t0
    rp = np.asarray(m.row_ptr, np.int32)
    col = np.asarray(m.col_idx, np.int32)
    val = np.asarray(m.val, np.float32)
    check_fingerprint(config, rp, col, val)
    matrix = CSR(rp.copy(), col.copy(), val.copy(), tuple(m.shape))
    host = transition(rp, col, m.shape)
    return Operator(config["name"], m.shape[0], host.nnz, config["dtype"], True,
                    matrix, host, build_s, None)
