"""Partitioners and legacy shard_map SpMV primitives (the paper's §5 base layer).

The distributed stack is layered:

1. **This module — partitioning + raw primitives.**  Row cuts
   (``row_balanced_partition`` = OpenMP ``schedule(static)`` on rows,
   ``nnz_balanced_partition`` = static scheduling balanced on work while
   preserving locality, the paper's winning recipe) and the original
   uniform-ELL shard_map kernels (``make_allgather_spmv``/``make_ring_spmv``
   over ``RowBlockELL``/``RingBlockELL``), kept as the paper-fidelity
   baseline and as oracles for the plan layer's tests.

2. **``core.distributed_plan`` — the compiled plan layer.**
   ``DistributedSpMVPlan`` splits each device's row block into the local
   column block (its own x shard) and the remote remainder, lets the
   ``perfmodel`` roofline pick the slab packing per partition, and offers
   three executor variants — ``allgather``, ``ring``, and ``overlap``
   (local compute concurrent with the first shard exchange, Schubert et
   al. arXiv:1106.5908) — each in SpMV and SpMM form, memoized on the
   matrix.  ``compile_distributed_plan`` below is the back-compat entry
   point and simply delegates there.

3. **Consumers.**  ``eigensolver.as_apply`` and
   ``serve.engine.SparseOperatorServer.register_distributed`` accept
   distributed plans interchangeably with single-device ``SpMVPlan``s.

The NUMA analogy from the paper holds throughout: each chip owns a row
block in local HBM (first-touch = sharded device_put by construction), and
the shared input vector's non-local accesses become ICI collectives.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map as _shard_map

from .formats import CSR

# ---------------------------------------------------------------------------
# partitioning (paper §5.2: scheduling / load balance)
# ---------------------------------------------------------------------------


def row_balanced_partition(n_rows: int, parts: int) -> np.ndarray:
    """Equal row counts (OpenMP ``schedule(static)`` on rows)."""
    bounds = np.linspace(0, n_rows, parts + 1).round().astype(np.int64)
    return bounds


def nnz_balanced_partition(m: CSR, parts: int) -> np.ndarray:
    """Cut rows so each part carries ~nnz/parts non-zeros (static schedule
    balanced on work, preserving locality — the paper's winning recipe).
    Cuts land on the row boundary *nearest* the ideal split point.

    Guaranteed never worse than ``row_balanced_partition``: if the greedy
    nnz cut loses on some degenerate pattern, the row-balanced bounds are
    returned instead (the property tests rely on this invariant).
    """
    rp = np.asarray(m.row_ptr, dtype=np.int64)
    total = rp[-1]
    targets = np.arange(1, parts, dtype=np.float64) * (total / parts)
    cuts = np.searchsorted(rp, targets, side="left")
    # round each cut to the nearer of the two adjacent row boundaries
    cuts = np.clip(cuts, 1, m.n_rows)
    lo = np.abs(rp[cuts - 1] - targets)
    hi = np.abs(rp[np.minimum(cuts, m.n_rows)] - targets)
    cuts = np.where(lo < hi, cuts - 1, cuts)
    bounds = np.concatenate([[0], cuts, [m.n_rows]]).astype(np.int64)
    bounds = np.maximum.accumulate(bounds)  # guard monotonicity on degenerate rows
    by_rows = row_balanced_partition(m.n_rows, parts)
    if partition_imbalance(m, by_rows) < partition_imbalance(m, bounds):
        return by_rows
    return bounds


def partition_imbalance(m: CSR, bounds: np.ndarray) -> float:
    """max part nnz / mean part nnz — 1.0 is perfect."""
    rp = np.asarray(m.row_ptr, dtype=np.int64)
    nnz = rp[bounds[1:]] - rp[bounds[:-1]]
    return float(nnz.max() / max(1.0, nnz.mean()))


# ---------------------------------------------------------------------------
# device-side block containers
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RowBlockELL:
    """Row-partitioned matrix as P stacked uniform ELL slabs.

    col/val: (P, rows_pp, W); row_map: (P, rows_pp) global row id (pad -> n);
    x is padded to P * x_shard.
    """

    col: np.ndarray
    val: np.ndarray
    row_map: np.ndarray
    n_rows: int
    n_cols: int
    nnz: int

    @property
    def parts(self) -> int:
        return int(self.col.shape[0])


def build_row_blocks(m: CSR, parts: int, balance: str = "nnz", pad_width_to: int = 1) -> RowBlockELL:
    bounds = (nnz_balanced_partition(m, parts) if balance == "nnz"
              else row_balanced_partition(m.n_rows, parts))
    lens = m.row_lengths()
    rows_pp = int(max(1, (bounds[1:] - bounds[:-1]).max()))
    W = int(max(1, lens.max())) if lens.size else 1
    W = -(-W // pad_width_to) * pad_width_to
    colb = np.zeros((parts, rows_pp, W), dtype=np.int32)
    valb = np.zeros((parts, rows_pp, W), dtype=np.asarray(m.val).dtype)
    rmap = np.full((parts, rows_pp), m.n_rows, dtype=np.int32)
    rp = np.asarray(m.row_ptr)
    ci, v = np.asarray(m.col_idx), np.asarray(m.val)
    for p in range(parts):
        r0, r1 = int(bounds[p]), int(bounds[p + 1])
        for i, r in enumerate(range(r0, r1)):
            L = int(lens[r])
            colb[p, i, :L] = ci[rp[r] : rp[r] + L]
            valb[p, i, :L] = v[rp[r] : rp[r] + L]
            rmap[p, i] = r
    return RowBlockELL(colb, valb, rmap, m.n_rows, m.shape[1], m.nnz)


@dataclass(frozen=True)
class RingBlockELL:
    """Row x column partitioned matrix for the ring (overlap) SpMV.

    col/val: (P, Q, rows_pp, W) with column indices local to block q.
    """

    col: np.ndarray
    val: np.ndarray
    row_map: np.ndarray  # (P, rows_pp)
    col_shard: int       # columns per shard (padded)
    n_rows: int
    n_cols: int
    nnz: int

    @property
    def parts(self) -> int:
        return int(self.col.shape[0])


def build_ring_blocks(m: CSR, parts: int, balance: str = "nnz") -> RingBlockELL:
    bounds = (nnz_balanced_partition(m, parts) if balance == "nnz"
              else row_balanced_partition(m.n_rows, parts))
    cs = -(-m.shape[1] // parts)
    lens = m.row_lengths()
    rows_pp = int(max(1, (bounds[1:] - bounds[:-1]).max()))
    rp = np.asarray(m.row_ptr)
    ci, v = np.asarray(m.col_idx), np.asarray(m.val)
    # per (p, q) ragged pieces first, then pad to the global max width
    pieces: list[list[tuple[np.ndarray, np.ndarray, np.ndarray]]] = []
    W = 1
    for p in range(parts):
        r0, r1 = int(bounds[p]), int(bounds[p + 1])
        row_pieces = []
        for q in range(parts):
            c0, c1 = q * cs, min((q + 1) * cs, m.shape[1])
            rows_l, cols_l, vals_l = [], [], []
            for i, r in enumerate(range(r0, r1)):
                seg = slice(rp[r], rp[r + 1])
                sel = (ci[seg] >= c0) & (ci[seg] < c1)
                k = int(sel.sum())
                if k:
                    rows_l.append(np.full(k, i, np.int32))
                    cols_l.append((ci[seg][sel] - c0).astype(np.int32))
                    vals_l.append(v[seg][sel])
                    W = max(W, k)
            row_pieces.append(
                (np.concatenate(rows_l) if rows_l else np.zeros(0, np.int32),
                 np.concatenate(cols_l) if cols_l else np.zeros(0, np.int32),
                 np.concatenate(vals_l) if vals_l else np.zeros(0, v.dtype))
            )
        pieces.append(row_pieces)
    colb = np.zeros((parts, parts, rows_pp, W), dtype=np.int32)
    valb = np.zeros((parts, parts, rows_pp, W), dtype=v.dtype)
    rmap = np.full((parts, rows_pp), m.n_rows, dtype=np.int32)
    for p in range(parts):
        r0, r1 = int(bounds[p]), int(bounds[p + 1])
        rmap[p, : r1 - r0] = np.arange(r0, r1, dtype=np.int32)
        for q in range(parts):
            rr, cc, vv = pieces[p][q]
            # pack each local row's entries consecutively
            fill = np.zeros(rows_pp, np.int64)
            for j in range(len(rr)):
                i = int(rr[j])
                colb[p, q, i, fill[i]] = cc[j]
                valb[p, q, i, fill[i]] = vv[j]
                fill[i] += 1
    return RingBlockELL(colb, valb, rmap, cs, m.n_rows, m.shape[1], m.nnz)


# ---------------------------------------------------------------------------
# shard_map SpMV variants
# ---------------------------------------------------------------------------


def _pad_x(x: jnp.ndarray, parts: int) -> tuple[jnp.ndarray, int]:
    n = x.shape[0]
    shard = -(-n // parts)
    return jnp.pad(x, (0, parts * shard - n)), shard


def make_allgather_spmv(blocks: RowBlockELL, mesh: Mesh, axis: str = "data"):
    """y = A @ x with x all-gathered once per SpMV (paper's shared invec).

    x enters sharded over ``axis``; each device gathers the full (padded) x,
    runs its uniform ELL slab, and emits its row-block result.  Returns
    ``f(x_padded) -> y`` plus the padded length.
    """
    parts = blocks.parts
    col = jnp.asarray(blocks.col)
    val = jnp.asarray(blocks.val)
    rmap = jnp.asarray(blocks.row_map)
    n = blocks.n_rows

    def local(colb, valb, rmapb, xloc):
        xfull = jax.lax.all_gather(xloc, axis, tiled=True)  # (P*shard,)
        g = jnp.take(xfull, colb[0], axis=0)                # (rows_pp, W)
        y = jnp.sum(valb[0] * g, axis=1)                    # (rows_pp,)
        return y[None], rmapb  # keep part axis for out_specs

    spec_blk = P(axis, None, None)
    spec_map = P(axis, None)
    f = _shard_map(
        local, mesh=mesh,
        in_specs=(spec_blk, spec_blk, spec_map, P(axis)),
        out_specs=(spec_map, spec_map),
    )

    def run(x: jnp.ndarray) -> jnp.ndarray:
        xp, _ = _pad_x(x, parts)
        yparts, rm = f(col, val, rmap, xp)
        out = jnp.zeros(n + 1, dtype=yparts.dtype)
        out = out.at[rm.reshape(-1)].add(yparts.reshape(-1))
        return out[:n]

    return run


def make_ring_spmv(blocks: RingBlockELL, mesh: Mesh, axis: str = "data"):
    """Overlapped ring SpMV: Q steps of (multiply local column block) +
    (collective-permute x shard), never materializing full x on any chip.

    Peak per-chip x footprint: 1 shard instead of the whole vector; the
    permute of step s+1 can overlap the multiply of step s (XLA async
    collectives) — this is the comm/compute-overlap variant of §5.
    """
    parts = blocks.parts
    col = jnp.asarray(blocks.col)
    val = jnp.asarray(blocks.val)
    rmap = jnp.asarray(blocks.row_map)
    n = blocks.n_rows
    perm = [(j, (j - 1) % parts) for j in range(parts)]

    def local(colb, valb, rmapb, xloc):
        colb, valb = colb[0], valb[0]          # (Q, rows_pp, W)
        xs = xloc                               # (shard,)
        me = jax.lax.axis_index(axis)

        def body(s, carry):
            y, xs = carry
            src = (me + s) % parts
            cb = jax.lax.dynamic_index_in_dim(colb, src, axis=0, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(valb, src, axis=0, keepdims=False)
            contrib = jnp.sum(vb * jnp.take(xs, cb, axis=0), axis=1)
            xs = jax.lax.ppermute(xs, axis, perm)
            return (y + contrib, xs)

        y0 = jnp.zeros(colb.shape[1], dtype=valb.dtype)
        if hasattr(jax.lax, "pcast"):  # newer jax: mark the accumulator varying
            y0 = jax.lax.pcast(y0, (axis,), to="varying")
        y, _ = jax.lax.fori_loop(0, parts, body, (y0, xs))
        return y[None], rmapb

    spec_blk = P(axis, None, None, None)
    spec_map = P(axis, None)
    f = _shard_map(
        local, mesh=mesh,
        in_specs=(spec_blk, spec_blk, spec_map, P(axis)),
        out_specs=(spec_map, spec_map),
    )

    def run(x: jnp.ndarray) -> jnp.ndarray:
        xp = jnp.pad(x, (0, parts * blocks.col_shard - x.shape[0]))
        yparts, rm = f(col, val, rmap, xp)
        out = jnp.zeros(n + 1, dtype=yparts.dtype)
        out = out.at[rm.reshape(-1)].add(yparts.reshape(-1))
        return out[:n]

    return run


def make_mesh_1d(axis: str = "data", n_devices: int | None = None) -> Mesh:
    devs = jax.devices()
    nd = n_devices or len(devs)
    return Mesh(np.array(devs[:nd]), (axis,))


# ---------------------------------------------------------------------------
# distributed execution plans — now in core.distributed_plan
# ---------------------------------------------------------------------------


def compile_distributed_plan(
    m: CSR,
    mesh: Mesh | None = None,
    *,
    strategy: str = "allgather",
    balance: str = "nnz",
    axis: str = "data",
    **kw,
):
    """Back-compat entry point: delegates to
    ``distributed_plan.compile_distributed_spmv_plan`` (``strategy`` is the
    plan layer's ``variant``; ``"overlap"`` is accepted here too).  Returns
    a ``DistributedSpMVPlan`` with SpMV *and* SpMM executors.
    """
    from .distributed_plan import compile_distributed_spmv_plan

    return compile_distributed_spmv_plan(m, mesh, variant=strategy,
                                         balance=balance, axis=axis, **kw)


# ---------------------------------------------------------------------------
# traffic accounting (for the parallel benchmarks / roofline)
# ---------------------------------------------------------------------------


def allgather_traffic_bytes(blocks: RowBlockELL, value_bytes: int = 4) -> dict:
    parts = blocks.parts
    shard = -(-blocks.n_cols // parts)
    stored = int(np.prod(blocks.col.shape))
    return {
        "hbm_stream": stored * (value_bytes + 4),
        "collective": parts * shard * value_bytes * (parts - 1),  # ring AG
        "per_chip_x": parts * shard * value_bytes,                # gathered copy
    }


def ring_traffic_bytes(blocks: RingBlockELL, value_bytes: int = 4) -> dict:
    parts = blocks.parts
    stored = int(np.prod(blocks.col.shape[1:]))  # per chip
    return {
        "hbm_stream": parts * stored * (value_bytes + 4),
        "collective": parts * blocks.col_shard * value_bytes * (parts - 1),
        "per_chip_x": blocks.col_shard * value_bytes,             # 1 shard only
    }


# ---------------------------------------------------------------------------
# subprocess selftest (run with XLA_FLAGS=--xla_force_host_platform_device_count=8)
# ---------------------------------------------------------------------------

if __name__ == "__main__":  # pragma: no cover - exercised via subprocess test
    import sys

    from .distributed_plan import compile_distributed_spmv_plan, VARIANTS
    from .matrices import holstein_hubbard_surrogate
    from .spmv import csr_spmv

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 4000
    m = holstein_hubbard_surrogate(n, seed=3)
    parts = len(jax.devices())
    mesh = make_mesh_1d()
    x = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)
    y_ref = np.asarray(csr_spmv(m, x))
    # legacy uniform-ELL primitives (the paper-fidelity baseline)
    for name, build, make in (
        ("allgather-legacy", build_row_blocks, make_allgather_spmv),
        ("ring-legacy", build_ring_blocks, make_ring_spmv),
    ):
        blocks = build(m, parts)
        run = jax.jit(make(blocks, mesh))
        y = np.asarray(run(x))
        err = float(np.max(np.abs(y - y_ref)) / max(1e-9, np.max(np.abs(y_ref))))
        status = "OK" if err < 1e-4 else "FAIL"
        print(f"{name}: devices={parts} rel_err={err:.2e} {status}")
        if err >= 1e-4:
            sys.exit(1)
    # plan layer: all three variants, model-chosen slab format
    for variant in VARIANTS:
        plan = compile_distributed_spmv_plan(m, mesh, variant=variant)
        err = float(np.max(np.abs(np.asarray(plan(x)) - y_ref))
                    / max(1e-9, np.max(np.abs(y_ref))))
        status = "OK" if err < 1e-4 else "FAIL"
        print(f"{variant}: devices={parts} slab={plan.slab_format} "
              f"local={plan.local_fraction:.2f} rel_err={err:.2e} {status}")
        if err >= 1e-4:
            sys.exit(1)
    imb_rows = partition_imbalance(m, row_balanced_partition(m.n_rows, parts))
    imb_nnz = partition_imbalance(m, nnz_balanced_partition(m, parts))
    print(f"imbalance rows={imb_rows:.3f} nnz={imb_nnz:.3f}")
    print("SELFTEST PASS")
