"""The Graph500 Kronecker generator, the PageRank transition matrix, and
PageRank by power iteration against a dense float64 reference."""
import numpy as np
import pytest

from repro.core.eigensolver import pagerank
from repro.core.matrices import kronecker_graph, pagerank_transition
from repro.core.plan import SpMVPlan
from repro.core.planconfig import PlanConfig
from repro.utils import spans


def _teleport(n, seed):
    t = 0.5 + np.random.default_rng(seed).random(n)
    return (t / t.sum()).astype(np.float32)


def _dense_pagerank(P, teleport, damping, tol, max_iters):
    """GAP's recurrence on a dense float64 matrix."""
    t = np.asarray(teleport, np.float64)
    x, changes = t, []
    for _ in range(max_iters):
        x_new = (1 - damping) * t + damping * (P @ x)
        changes.append(np.abs(x_new - x).sum())
        x = x_new
        if changes[-1] < tol:
            break
    return x, np.asarray(changes)


# --- the generator --------------------------------------------------------------

@pytest.mark.parametrize("scale", [8, 10, 12])
def test_kronecker_graph_is_a_simple_undirected_graph(scale):
    g = kronecker_graph(scale, seed=3)
    n = 1 << scale
    assert g.shape == (n, n)
    rp, col = np.asarray(g.row_ptr), np.asarray(g.col_idx)
    rows = np.repeat(np.arange(n), np.diff(rp))
    assert not np.any(rows == col)                         # no self-loops
    # strictly increasing columns in each row: no duplicate edges
    same_row = rows[1:] == rows[:-1]
    assert np.all(col[1:][same_row] > col[:-1][same_row])
    d = g.to_dense()
    assert np.array_equal(d, d.T) and set(np.unique(d)) == {0.0, 1.0}
    # each of the 16 n edges stores at most two entries; self-loops and
    # duplicates take a share that shrinks with the scale
    assert 0.5 * 32 * n < g.nnz <= 32 * n
    assert np.diff(rp).max() > 5 * g.nnz / n                 # a heavy tail


def _kronecker_loop(scale, edge_factor, initiator, seed):
    """kronecker_generator.m edge by edge, with the same draws in the same
    order; then GAP's symmetrise, self-loop and duplicate removal."""
    a, b, c = initiator
    n, m = 1 << scale, edge_factor << scale
    rng = np.random.default_rng(seed)
    ab, c_norm, a_norm = a + b, c / (1 - (a + b)), a / (a + b)
    ij = [[0, 0] for _ in range(m)]
    for bit in range(scale):
        r1, r2 = rng.random(m), rng.random(m)
        for e in range(m):
            ii_bit = r1[e] > ab
            jj_bit = r2[e] > (c_norm if ii_bit else a_norm)
            ij[e][0] += int(ii_bit) << bit
            ij[e][1] += int(jj_bit) << bit
    perm = rng.permutation(n)
    edges = {(int(perm[i]), int(perm[j])) for i, j in ij if perm[i] != perm[j]}
    edges |= {(j, i) for i, j in edges}
    d = np.zeros((n, n), np.float32)
    for i, j in edges:
        d[i, j] = 1.0
    return d


@pytest.mark.parametrize("initiator", [(0.57, 0.19, 0.19), (0.25, 0.25, 0.25)])
def test_kronecker_graph_matches_the_reference_edge_by_edge(initiator):
    d = _kronecker_loop(6, 16, initiator, seed=11)
    assert np.array_equal(kronecker_graph(6, initiator=initiator, seed=11).to_dense(), d)


def test_kronecker_graph_is_deterministic_per_seed():
    a, b, c = kronecker_graph(10, seed=5), kronecker_graph(10, seed=5), kronecker_graph(10, seed=6)
    assert np.array_equal(a.row_ptr, b.row_ptr) and np.array_equal(a.col_idx, b.col_idx)
    assert not (np.array_equal(a.row_ptr, c.row_ptr) and np.array_equal(a.col_idx, c.col_idx))


def test_kronecker_column_bit_is_drawn_against_the_row_bit():
    """With A = 0 and B = C = 1/2 the reference's rule sets exactly one of
    the two bits at every level (c_norm = 1, a_norm = 0), so every edge
    joins a vertex to its bitwise complement: a perfect matching."""
    scale = 9
    g = kronecker_graph(scale, initiator=(0.0, 0.5, 0.5), seed=1)
    assert np.array_equal(np.diff(np.asarray(g.row_ptr)), np.ones(1 << scale))


def test_pagerank_transition_is_column_stochastic():
    g = kronecker_graph(10, seed=2)
    P = pagerank_transition(g)
    d = P.to_dense().astype(np.float64)
    deg = g.to_dense().sum(axis=0)
    assert (deg == 0).any() and (deg > 0).any()
    np.testing.assert_allclose(d.sum(axis=0)[deg > 0], 1.0, rtol=1e-6)
    assert not d[:, deg == 0].any()
    rows, cols = np.nonzero(d)
    np.testing.assert_allclose(d[rows, cols], 1.0 / deg[cols], rtol=1e-7)


# --- the solver ---------------------------------------------------------------

@pytest.fixture(scope="module")
def graph10():
    P = pagerank_transition(kronecker_graph(10, seed=0))
    return P, P.to_dense().astype(np.float64)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("damping,max_iters,stop", [(0.85, 20, "tol"), (0.85, 6, "max_iters"),
                                                    (0.5, 20, "tol")])
@pytest.mark.parametrize("path", ["plan_auto", "container"])
def test_pagerank_matches_dense_float64(graph10, seed, damping, max_iters, stop, path):
    P, dense = graph10
    n = P.shape[0]
    t = _teleport(n, seed)
    ref, ref_changes = _dense_pagerank(dense, t, damping, 1e-4, max_iters)
    op = SpMVPlan.compile(P, PlanConfig(format="auto")) if path == "plan_auto" else P
    r = pagerank(op, n, t, damping=damping, tol=1e-4, max_iters=max_iters)
    assert (len(ref_changes) < max_iters) == (stop == "tol")
    assert r.n_iterations == r.n_spmv == len(ref_changes)
    x = np.asarray(r.scores, np.float64)
    assert np.abs(x - ref).sum() / np.abs(ref).sum() < 1e-5
    np.testing.assert_allclose(r.l1_changes, ref_changes, rtol=1e-3)


def test_pagerank_emits_one_solve_span_and_a_step_and_sync_per_iteration(graph10):
    P, _ = graph10
    n = P.shape[0]
    before = spans.snapshot()["spans"]
    r = pagerank(P, n, _teleport(n, 4), max_iters=7)
    after = spans.snapshot()["spans"]

    def added(name):
        return after.get(name, [0])[0] - before.get(name, [0])[0]
    assert r.n_iterations == 7
    assert added("pagerank") == 1
    assert added("pagerank.step") == added("pagerank.sync") == 7


def test_pagerank_refuses_a_teleport_of_the_wrong_length(graph10):
    P, _ = graph10
    with pytest.raises(ValueError, match="teleport"):
        pagerank(P, P.shape[0], np.ones(3, np.float32))
