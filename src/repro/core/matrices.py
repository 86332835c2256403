"""Test matrices: the paper's Holstein-Hubbard Hamiltonian + synthetic patterns.

Two generators for the paper's physics matrix:

1. ``holstein_hubbard_exact`` — the *real* model Hamiltonian
       H = -t  Σ_{<ij>σ} (c†_iσ c_jσ + h.c.)
           + U  Σ_i n_i↑ n_i↓
           + gω₀ Σ_i (b†_i + b_i)(n_i↑ + n_i↓)
           + ω₀ Σ_i b†_i b_i
   on an L-site chain with N_up/N_dn electrons and a truncated phonon space.
   Exactly diagonalizable at small dimension -> validates the eigensolver and
   gives a *physically faithful* sparsity pattern (dense secondary diagonals
   from the phonon ladder + scattered hopping band, symmetric; cf. Fig 5).

2. ``holstein_hubbard_surrogate`` — a scalable pattern-faithful surrogate
   reproducing the Fig-5 statistics at any N: ~14 nnz/row, ~60 % of nnz in
   12 dense secondary diagonals, remainder scattered over a band, symmetric.

Plus the Graph500 Kronecker graph and its PageRank transition matrix
(``kronecker_graph``, ``pagerank_transition``), and generic pattern
generators used by tests and microbenchmarks.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass

import numpy as np

from .formats import COO, CSR

# ---------------------------------------------------------------------------
# exact Holstein-Hubbard
# ---------------------------------------------------------------------------


def _fermion_basis(L: int, n: int) -> np.ndarray:
    """All L-bit masks with n bits set, ascending."""
    states = [m for m in range(1 << L) if bin(m).count("1") == n]
    return np.asarray(states, dtype=np.int64)


def _hop_sign(state: int, i: int, j: int) -> int:
    """Fermionic sign for c†_j c_i (i occupied, j empty), Jordan-Wigner."""
    lo, hi = (i, j) if i < j else (j, i)
    mask = ((1 << hi) - 1) ^ ((1 << (lo + 1)) - 1)  # bits strictly between
    return -1 if bin(state & mask).count("1") % 2 else 1


@dataclass(frozen=True)
class HolsteinHubbardParams:
    L: int = 4          # chain sites
    n_up: int = 1
    n_dn: int = 1
    max_phonon: int = 2  # per-site phonon cutoff
    max_total_phonon: int | None = None  # optional global cutoff
    t: float = 1.0
    U: float = 4.0
    g: float = 0.5
    omega0: float = 1.0
    periodic: bool = True


def holstein_hubbard_exact(p: HolsteinHubbardParams = HolsteinHubbardParams()) -> CSR:
    """Build the exact Hamiltonian in CSR (fp64, symmetric)."""
    L = p.L
    ups = _fermion_basis(L, p.n_up)
    dns = _fermion_basis(L, p.n_dn)
    up_index = {int(s): k for k, s in enumerate(ups)}
    dn_index = {int(s): k for k, s in enumerate(dns)}
    # phonon configurations
    phonons = [
        ph
        for ph in itertools.product(range(p.max_phonon + 1), repeat=L)
        if p.max_total_phonon is None or sum(ph) <= p.max_total_phonon
    ]
    ph_index = {ph: k for k, ph in enumerate(phonons)}
    n_up_s, n_dn_s, n_ph = len(ups), len(dns), len(phonons)
    dim = n_up_s * n_dn_s * n_ph

    def idx(iu: int, idn: int, ip: int) -> int:
        return (iu * n_dn_s + idn) * n_ph + ip

    rows: list[int] = []
    cols: list[int] = []
    vals: list[float] = []

    def add(r: int, c: int, v: float):
        if v != 0.0:
            rows.append(r)
            cols.append(c)
            vals.append(v)

    bonds = [(i, i + 1) for i in range(L - 1)]
    if p.periodic and L > 2:
        bonds.append((L - 1, 0))

    for iu, su in enumerate(ups):
        su = int(su)
        for idn, sd in enumerate(dns):
            sd = int(sd)
            n_docc = bin(su & sd).count("1")
            n_el_site = [((su >> i) & 1) + ((sd >> i) & 1) for i in range(L)]
            for ip, ph in enumerate(phonons):
                r = idx(iu, idn, ip)
                # diagonal: U double occupancy + phonon energy
                add(r, r, p.U * n_docc + p.omega0 * sum(ph))
                # electron-phonon coupling: g*w0*(b†+b)_i * n_i
                for i in range(L):
                    if n_el_site[i] == 0:
                        continue
                    amp = p.g * p.omega0 * n_el_site[i]
                    if ph[i] < p.max_phonon:
                        ph2 = ph[:i] + (ph[i] + 1,) + ph[i + 1 :]
                        ip2 = ph_index.get(ph2)
                        if ip2 is not None:
                            add(r, idx(iu, idn, ip2), amp * np.sqrt(ph[i] + 1))
                    if ph[i] > 0:
                        ph2 = ph[:i] + (ph[i] - 1,) + ph[i + 1 :]
                        ip2 = ph_index.get(ph2)
                        if ip2 is not None:
                            add(r, idx(iu, idn, ip2), amp * np.sqrt(ph[i]))
                # hopping (spin up)
                for (a, b) in bonds:
                    for (src, dst) in ((a, b), (b, a)):
                        if (su >> src) & 1 and not (su >> dst) & 1:
                            s2 = su ^ (1 << src) ^ (1 << dst)
                            sgn = _hop_sign(su, src, dst)
                            add(r, idx(up_index[s2], idn, ip), -p.t * sgn)
                        if (sd >> src) & 1 and not (sd >> dst) & 1:
                            s2 = sd ^ (1 << src) ^ (1 << dst)
                            sgn = _hop_sign(sd, src, dst)
                            add(r, idx(iu, dn_index[s2], ip), -p.t * sgn)

    coo = COO(
        np.asarray(rows, np.int32),
        np.asarray(cols, np.int32),
        np.asarray(vals, np.float64),
        (dim, dim),
    )
    return CSR.from_coo(coo)


# ---------------------------------------------------------------------------
# scalable pattern-faithful surrogate (Fig 5 statistics)
# ---------------------------------------------------------------------------


def holstein_hubbard_surrogate(
    n: int,
    nnz_per_row: float = 14.0,
    n_secondary_diags: int = 12,
    frac_in_diags: float = 0.60,
    diag_occupancy: float | None = None,
    band_frac: float = 0.02,
    seed: int = 0,
    dtype=np.float32,
) -> CSR:
    """Synthetic symmetric matrix reproducing the Fig-5 structure at size n.

    * full main diagonal,
    * ``n_secondary_diags`` dense secondary diagonals (6 symmetric ± pairs)
      near the outer band edge carrying ``frac_in_diags`` of all nnz,
    * the rest scattered uniformly over a band of half-width
      ``band_frac * n`` ("several hundred secondary diagonals" in the paper).
    """
    rng = np.random.default_rng(seed)
    band = max(n_secondary_diags * 4, int(band_frac * n))
    band = min(band, n - 1)
    total_target = nnz_per_row * n
    n_pairs = n_secondary_diags // 2
    # secondary-diagonal offsets: spread over the outer half of the band
    offs = np.unique(
        np.linspace(band // 2, band, n_pairs, dtype=np.int64)
    )
    while len(offs) < n_pairs:  # tiny n edge case
        offs = np.unique(np.concatenate([offs, offs[-1:] + 1]))
    offs = offs[:n_pairs]
    diag_target = frac_in_diags * total_target
    if diag_occupancy is None:
        # each ± pair of occupancy q contributes ~2*q*(n-off) entries
        avail = 2.0 * np.sum(n - offs)
        diag_occupancy = min(0.95, diag_target / max(1.0, avail))

    rows_list, cols_list, vals_list = [], [], []

    # main diagonal (always fully occupied: Hamiltonian diagonal)
    i = np.arange(n, dtype=np.int64)
    rows_list.append(i)
    cols_list.append(i)
    vals_list.append(rng.standard_normal(n) + 4.0)  # diagonally dominant-ish

    # dense secondary diagonals (upper triangle; mirrored below)
    for off in offs:
        ln = n - int(off)
        keep = rng.random(ln) < diag_occupancy
        ii = np.nonzero(keep)[0].astype(np.int64)
        vv = rng.standard_normal(len(ii))
        rows_list.append(ii)
        cols_list.append(ii + off)
        vals_list.append(vv)

    # scattered band entries (upper triangle)
    used = sum(len(r) for r in rows_list[1:]) * 2 + n
    remaining = max(0, int(total_target) - used)
    n_scatter = remaining // 2  # upper-triangle count (mirrored)
    ri = rng.integers(0, n, size=n_scatter)
    doff = rng.integers(1, band + 1, size=n_scatter)
    ci = ri + doff
    ok = ci < n
    ri, ci = ri[ok].astype(np.int64), ci[ok].astype(np.int64)
    vv = rng.standard_normal(len(ri)) * 0.5
    rows_list.append(ri)
    cols_list.append(ci)
    vals_list.append(vv)

    rows = np.concatenate(rows_list)
    cols = np.concatenate(cols_list)
    vals = np.concatenate(vals_list).astype(dtype)
    # symmetrize: mirror strictly-upper entries
    upper = cols > rows
    rows_f = np.concatenate([rows, cols[upper]])
    cols_f = np.concatenate([cols, rows[upper]])
    vals_f = np.concatenate([vals, vals[upper]])
    # deduplicate (scattered entries may collide with diagonals): sum dups
    key = rows_f * n + cols_f
    uniq, inv = np.unique(key, return_inverse=True)
    vsum = np.zeros(len(uniq), dtype=np.float64)
    np.add.at(vsum, inv, vals_f.astype(np.float64))
    rows_u = (uniq // n).astype(np.int32)
    cols_u = (uniq % n).astype(np.int32)
    return CSR.from_coo(COO(rows_u, cols_u, vsum.astype(dtype), (n, n)))


# ---------------------------------------------------------------------------
# Graph500 Kronecker graph (the GAP benchmark suite's ``kron`` input)
# ---------------------------------------------------------------------------


def kronecker_graph(scale: int, edge_factor: int = 16,
                    initiator: tuple = (0.57, 0.19, 0.19), seed: int = 0) -> CSR:
    """The Graph500 Kronecker graph on ``2**scale`` vertices, as the
    undirected adjacency matrix GAP builds from it (values 1.0).

    Follows the Graph500 reference ``kronecker_generator.m``: each of the
    ``edge_factor * 2**scale`` edges takes one quadrant draw per bit of
    its endpoints (row bit set with probability ``1 - (A + B)``, then the
    column bit with ``C / (1 - (A + B))`` or ``A / (A + B)`` conditioned
    on it; ``D`` is the rest), then the vertex labels are permuted at
    random.  The reference's shuffle of the edge order is left out: the
    graph is the same set of edges.  Then, as GAP builds an undirected
    graph: symmetrise, drop self-loops and duplicate edges.  Vectorised
    over the edges; the only Python loop runs over the ``scale`` bits.
    """
    a, b, c = initiator
    n = 1 << scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ii = np.zeros(m, np.int64)
    jj = np.zeros(m, np.int64)
    u = np.empty(m)                     # work buffers, reused for every bit
    ii_bit, lo_bit, hi_bit = (np.empty(m, bool) for _ in range(3))
    shifted = np.empty(m, np.int64)
    for bit in range(scale):
        np.greater(rng.random(out=u), ab, out=ii_bit)
        rng.random(out=u)
        np.greater(u, a_norm, out=lo_bit)               # the column bit where ii_bit is 0
        np.greater(u, c_norm, out=hi_bit)               # ... and where it is 1
        jj_bit = np.where(ii_bit, hi_bit, lo_bit)
        ii |= np.left_shift(ii_bit, bit, out=shifted, dtype=np.int64)
        jj |= np.left_shift(jj_bit, bit, out=shifted, dtype=np.int64)
    perm = rng.permutation(n)
    ii, jj = perm[ii], perm[jj]
    keep = ii != jj
    ii, jj = ii[keep], jj[keep]
    # both directions of every edge, deduplicated and in row-major order
    rows, cols = np.divmod(np.unique(np.concatenate([ii * n + jj, jj * n + ii])), n)
    row_ptr = np.zeros(n + 1, np.int32)
    np.cumsum(np.bincount(rows, minlength=n), out=row_ptr[1:])
    return CSR(row_ptr, cols.astype(np.int32), np.ones(len(cols), np.float32), (n, n))


def pagerank_transition(adj: CSR, dtype=np.float32) -> CSR:
    """The column-stochastic PageRank operator ``P = A D^-1`` of an
    adjacency ``adj``: the value at ``(i, j)`` is ``1 / deg(j)``, with
    ``deg(j)`` the stored entries of column ``j``.  Columns of vertices
    without edges are empty (their mass leaks, as in GAP)."""
    col = np.asarray(adj.col_idx)
    deg = np.bincount(col, minlength=adj.shape[1]).astype(np.float64)
    val = (1.0 / deg[col]).astype(dtype)
    return CSR(np.asarray(adj.row_ptr).copy(), col.copy(), val, tuple(adj.shape))


# ---------------------------------------------------------------------------
# generic generators (tests / benchmarks)
# ---------------------------------------------------------------------------


def random_sparse(n_rows: int, n_cols: int, nnz_per_row: int, seed: int = 0,
                  dtype=np.float32) -> CSR:
    """Uniform random pattern with exactly nnz_per_row entries per row."""
    rng = np.random.default_rng(seed)
    k = min(nnz_per_row, n_cols)
    cols = np.stack([rng.choice(n_cols, size=k, replace=False) for _ in range(n_rows)])
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), k)
    vals = rng.standard_normal(n_rows * k).astype(dtype)
    return CSR.from_coo(COO(rows.astype(np.int32), cols.reshape(-1).astype(np.int32), vals, (n_rows, n_cols)))


def random_banded(n: int, half_bandwidth: int, density: float, seed: int = 0,
                  dtype=np.float32) -> CSR:
    rng = np.random.default_rng(seed)
    i = np.arange(n, dtype=np.int64)
    rows_list, cols_list = [], []
    for off in range(-half_bandwidth, half_bandwidth + 1):
        lo, hi = max(0, -off), min(n, n - off)
        keep = rng.random(hi - lo) < density
        ii = i[lo:hi][keep]
        rows_list.append(ii)
        cols_list.append(ii + off)
    rows = np.concatenate(rows_list)
    cols = np.concatenate(cols_list)
    vals = rng.standard_normal(len(rows)).astype(dtype)
    return CSR.from_coo(COO(rows.astype(np.int32), cols.astype(np.int32), vals, (n, n)))


def laplacian_2d(nx: int, ny: int, dtype=np.float64) -> CSR:
    """Standard 5-point stencil on an nx×ny grid (classic well-known oracle)."""
    n = nx * ny
    rows, cols, vals = [], [], []
    for y in range(ny):
        for x in range(nx):
            r = y * nx + x
            rows.append(r); cols.append(r); vals.append(4.0)
            for dx, dy in ((1, 0), (-1, 0), (0, 1), (0, -1)):
                xx, yy = x + dx, y + dy
                if 0 <= xx < nx and 0 <= yy < ny:
                    rows.append(r); cols.append(yy * nx + xx); vals.append(-1.0)
    return CSR.from_coo(COO(np.asarray(rows, np.int32), np.asarray(cols, np.int32),
                            np.asarray(vals, dtype), (n, n)))


def laplacian_3d(nx: int, ny: int, nz: int, dtype=np.float64) -> CSR:
    """Standard 7-point stencil on an nx×ny×nz grid.

    The 3-D analogue of ``laplacian_2d``: same well-known oracle, but the
    ±nx·ny couplings put the outer diagonals much further out — the
    bandwidth grows with the *plane* size, so the input-vector working set
    no longer fits a cache line window (the regime the paper's stride
    penalties model).
    """
    n = nx * ny * nz
    idx = np.arange(n, dtype=np.int64)
    x = idx % nx
    y = (idx // nx) % ny
    z = idx // (nx * ny)
    rows_list = [idx]
    cols_list = [idx]
    vals_list = [np.full(n, 6.0)]
    for axis, coord, extent, stride in (
            (0, x, nx, 1), (1, y, ny, nx), (2, z, nz, nx * ny)):
        for sgn in (+1, -1):
            ok = (coord + sgn >= 0) & (coord + sgn < extent)
            rows_list.append(idx[ok])
            cols_list.append(idx[ok] + sgn * stride)
            vals_list.append(np.full(int(ok.sum()), -1.0))
    rows = np.concatenate(rows_list)
    cols = np.concatenate(cols_list)
    vals = np.concatenate(vals_list).astype(dtype)
    return CSR.from_coo(COO(rows.astype(np.int32), cols.astype(np.int32), vals, (n, n)))


def dense_stripe(n: int, stripe_width: int, stripe_start: int | None = None,
                 seed: int = 0, dtype=np.float32) -> CSR:
    """Near-dense vertical stripe + full main diagonal.

    Every row touches the same ``stripe_width`` contiguous columns, so row
    lengths are constant (zero padding in any jagged format) and the
    input-vector gather hits one small, fully reused window — the opposite
    corner of the corpus from the power-law pattern.  Offsets ``col - row``
    differ on every row, so diagonal storage is the *worst* choice here.
    """
    rng = np.random.default_rng(seed)
    c0 = (n - stripe_width) // 2 if stripe_start is None else stripe_start
    assert 0 <= c0 and c0 + stripe_width <= n
    i = np.arange(n, dtype=np.int64)
    # diagonal entries only where the stripe doesn't already cover column i
    diag = i[(i < c0) | (i >= c0 + stripe_width)]
    rows = np.concatenate([diag, np.repeat(i, stripe_width)])
    cols = np.concatenate([diag, np.tile(np.arange(c0, c0 + stripe_width, dtype=np.int64), n)])
    vals = rng.standard_normal(len(rows)).astype(dtype)
    vals[: len(diag)] += 4.0  # keep the diagonal dominant-ish
    return CSR.from_coo(COO(rows.astype(np.int32), cols.astype(np.int32), vals, (n, n)))


def power_law_rows(n: int, n_cols: int, mean_nnz: float = 8.0, alpha: float = 1.5,
                   seed: int = 0, dtype=np.float32, max_nnz: int | None = None) -> CSR:
    """Strongly imbalanced row lengths (Zipf-ish) — the load-balancing stressor
    for partitioners (paper §5.2 scheduling discussion).

    ``max_nnz`` caps the heaviest rows (Zipf at alpha<=2 has unbounded mean,
    so without a cap single rows can swallow the whole column range and any
    padded format degenerates to dense)."""
    rng = np.random.default_rng(seed)
    cap = n_cols if max_nnz is None else min(n_cols, max_nnz)
    raw = rng.zipf(alpha, size=n).astype(np.float64)
    lens = np.minimum(cap, np.maximum(1, (raw * mean_nnz / max(1e-9, raw.mean())).astype(np.int64)))
    rows = np.repeat(np.arange(n, dtype=np.int64), lens)
    cols = rng.integers(0, n_cols, size=int(lens.sum()))
    # dedup within row not required for benchmarks; sum dups via CSR.from_coo path
    vals = rng.standard_normal(len(rows)).astype(dtype)
    return CSR.from_coo(COO(rows.astype(np.int32), cols.astype(np.int32), vals, (n, n_cols)))


def block_sparse_dense(m: int, n: int, block: tuple[int, int], block_density: float,
                       seed: int = 0, dtype=np.float32) -> np.ndarray:
    """Dense array with a random block-sparse support — BSR's home turf
    (structured-sparse weight matrices)."""
    rng = np.random.default_rng(seed)
    bm, bn = block
    assert m % bm == 0 and n % bn == 0
    mask = rng.random((m // bm, n // bn)) < block_density
    d = rng.standard_normal((m, n)).astype(dtype)
    d *= np.kron(mask, np.ones((bm, bn), dtype=dtype))
    return d
