"""Distributed SpMV plans: compile the partition once, overlap comm with compute.

The paper's parallel story (Sec. 5) is that SpMV across NUMA domains is bound
by two things: non-local accesses to the shared input vector, and load
imbalance between domains.  Its follow-ups make the remedies explicit:
Schubert et al. (arXiv:1106.5908) *overlap* the exchange of remote x entries
with the multiplication of the purely local matrix part, and Kreutzer et al.
(arXiv:1307.6209) choose the slab storage format *per partition* rather than
globally.  This module is both ideas as a compiled plan layer on a 1-D device
mesh:

* **Compile time** — rows are cut by ``nnz_balanced_partition`` (work balance
  without losing locality); each device's row block is split against the
  column blocks of the mesh, so the sub-block that hits the device's *own*
  x shard (the local column block) is distinguished from the remote
  remainder; per-partition row-length statistics are fed through the
  ``perfmodel`` roofline to pick the slab packing (padded-ELL vs flat
  SELL-style) instead of hard-coding ELL.

* **Run time** — three executor variants over the same shard layout:

  - ``allgather``: one all-gather of x per SpMV, then one slab multiply —
    the paper's shared-input-vector baseline;
  - ``ring``: P steps of (multiply the column slab matching the currently
    held x shard, collective-permute the shard onward) — full x never
    materializes on any chip;
  - ``overlap``: the ring, unrolled, with the first permute issued *before*
    the local column block's multiply, so the ICI transfer of the first
    remote shard proceeds while the device computes the only work that
    needs no communication (the 1106.5908 scheme).

Every variant exists in SpMV (``plan(x)``) and SpMM (``plan.spmm(X)``,
multi-vector) form; executors are jitted once and plans are memoized on the
matrix container, mirroring ``core.plan.SpMVPlan``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import spans
from ..utils.hw import ChipSpec, TPU_V5E
from . import perfmodel as PM
from .distributed import make_mesh_1d, nnz_balanced_partition, row_balanced_partition
from .formats import CSR, pack_chunks_flat, sigma_sort_order
from .plan import PlanReport

SLAB_FORMATS = ("ell", "sell")
VARIANTS = ("allgather", "ring", "overlap")

# build counters in utils.spans, mirroring core.spmv.precompute_stats:
# regression tests assert each shard is packed exactly once per
# (matrix, plan-key)
_PACK_KEYS = ("shard_packs", "format_selections")


def pack_stats() -> dict:
    """The shard-packing build counters (for caching regressions)."""
    counters = spans.snapshot()["counters"]
    return {k: counters.get("pack." + k, 0) for k in _PACK_KEYS}


# ---------------------------------------------------------------------------
# per-shard format selection (perfmodel-driven, Kreutzer-style)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShardReport:
    """What the model saw and chose for one row partition."""

    part: int
    rows: int
    nnz: int
    local_nnz: int          # entries hitting the shard's own x block
    remote_nnz: int         # entries needing communicated x shards
    format: str             # the model's per-shard choice
    predicted_time_s: float  # of the chosen format
    times: dict             # {format: predicted time} for all candidates


def plan_shard_formats(
    m: CSR,
    bounds: np.ndarray,
    *,
    C: int = 8,
    am: PM.AccessModel | None = None,
    chip: ChipSpec = TPU_V5E,
    formats: tuple = SLAB_FORMATS,
) -> list[ShardReport]:
    """Run the roofline over each partition's row-length profile.

    This is ``plan_all_formats`` restricted to the slab formats a stacked
    SPMD executor can express, evaluated per partition: ELL pays the
    partition's padding ratio, flat SELL pays only per-chunk padding but
    adds the row-index stream of a segment-sum.

    Args:
        m: the full CSR matrix being partitioned.
        bounds: (P+1,) row partition bounds from a partitioner.
        C: SELL chunk height used for the padding estimate.
        am / chip: access model + roofline parameters.
        formats: candidate slab packings to evaluate.

    Returns:
        One ``ShardReport`` per partition, carrying the per-format
        predicted times and the per-shard best choice.
    """
    spans.count("pack.format_selections")
    if am is None:
        am = PM.access_model_for(m, chip)
    parts = len(bounds) - 1
    lens = m.row_lengths()
    rp = np.asarray(m.row_ptr, dtype=np.int64)
    ci = np.asarray(m.col_idx)
    cs = -(-m.shape[1] // parts)
    reports = []
    for p in range(parts):
        r0, r1 = int(bounds[p]), int(bounds[p + 1])
        lens_p = lens[r0:r1]
        nnz_p = int(lens_p.sum())
        npr = float(lens_p.mean()) if lens_p.size else 0.0
        seg = ci[rp[r0]:rp[r1]]
        local = int(((seg >= p * cs) & (seg < (p + 1) * cs)).sum())
        times = {}
        for fmt in formats:
            # the pad-ratio/balance accounting is perfmodel.balance_slab —
            # one implementation shared with the kernel registry's slab
            # entries (this loop used to rebuild the flat-SELL access model
            # inline)
            if fmt == "ell":
                pad = PM.ell_pad_ratio(lens_p)
            elif fmt == "sell":
                pad = PM.sell_pad_ratio(lens_p, C, max(1, len(lens_p)))
            else:
                raise ValueError(f"unknown slab format {fmt!r}")
            bal = PM.balance_slab(fmt, am, pad, npr)
            times[fmt] = PM.predict(fmt, bal, max(1, nnz_p), chip).time_s
        best = min(times, key=times.get)
        reports.append(ShardReport(
            part=p, rows=r1 - r0, nnz=nnz_p, local_nnz=local,
            remote_nnz=nnz_p - local, format=best,
            predicted_time_s=times[best], times=times,
        ))
    return reports


def select_slab_format(reports: list[ShardReport], formats: tuple = SLAB_FORMATS) -> str:
    """One SPMD program runs on every device, so the plan must commit to a
    single slab format; pick the one minimizing the *straggler* (max over
    shards) predicted time — per-shard preferences stay in the reports."""
    return min(formats, key=lambda f: max(r.times[f] for r in reports))


# ---------------------------------------------------------------------------
# shard slab containers + packing
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShardSlabs:
    """Row-partitioned matrix packed as P stacked per-device slabs.

    ``q`` indexes column blocks: ``q_blocks == 1`` stores each row block
    whole with *global* column indices (the allgather layout); ``q_blocks ==
    parts`` splits it against the mesh's x shards with *shard-local* column
    indices (the ring/overlap layout, block ``q == p`` being the local
    column block).

    ``pack == "ell"``: col/val are (P, Q, rows_pp, W) padded 2-D slabs.
    ``pack == "sell"``: col/val/rid are (P, Q, L) flat SELL-C slabs — rows
    sigma-sorted within the partition, chunked by C, each chunk padded to
    its own width; ``rid`` holds partition-local row ids (pad -> rows_pp).
    """

    pack: str
    col: np.ndarray
    val: np.ndarray
    rid: np.ndarray | None     # flat pack only
    row_map: np.ndarray        # (P, rows_pp) global row ids (pad -> n_rows)
    bounds: np.ndarray         # (P+1,) row partition bounds
    col_shard: int             # x shard length (padded)
    rows_pp: int
    n_rows: int
    n_cols: int
    nnz: int

    @property
    def parts(self) -> int:
        return int(self.col.shape[0])

    @property
    def q_blocks(self) -> int:
        return int(self.col.shape[1])

    @property
    def stored(self) -> int:
        """Streamed (padded) elements per SpMV across all devices."""
        return int(np.prod(self.col.shape))


def _block_rows(rp, ci, v, r0, r1, c0, c1, local_cols):
    """Per-row (cols, vals) of the (r0:r1, c0:c1) block, cols block-local."""
    out = []
    for r in range(r0, r1):
        seg = slice(rp[r], rp[r + 1])
        cseg, vseg = ci[seg], v[seg]
        if local_cols:
            sel = (cseg >= c0) & (cseg < c1)
            cseg, vseg = cseg[sel] - c0, vseg[sel]
        out.append((cseg.astype(np.int32), vseg))
    return out


def pack_shard_slabs(
    m: CSR,
    parts: int,
    *,
    balance: str = "nnz",
    pack: str = "ell",
    local_cols: bool = False,
    C: int = 8,
    bounds: np.ndarray | None = None,
) -> ShardSlabs:
    """Partition ``m`` into P row blocks and pack each as a device slab.

    ``local_cols=False`` produces the allgather layout (one q block, global
    column ids); ``local_cols=True`` the ring/overlap layout (P q blocks,
    ids local to each x shard).  Packing each shard happens exactly once per
    call — plan memoization keeps it once per (matrix, key) lifetime.
    """
    if pack not in SLAB_FORMATS:
        raise ValueError(f"unknown slab pack {pack!r}")
    if bounds is None:
        bounds = (nnz_balanced_partition(m, parts) if balance == "nnz"
                  else row_balanced_partition(m.n_rows, parts))
    rows_pp = int(max(1, (bounds[1:] - bounds[:-1]).max()))
    cs = -(-m.shape[1] // parts)
    Q = parts if local_cols else 1
    rp = np.asarray(m.row_ptr, dtype=np.int64)
    ci, v = np.asarray(m.col_idx), np.asarray(m.val)
    row_map = np.full((parts, rows_pp), m.n_rows, dtype=np.int32)

    # gather ragged per-(p, q) blocks first; pad uniformly afterwards
    blocks: list[list[list[tuple[np.ndarray, np.ndarray]]]] = []
    for p in range(parts):
        spans.count("pack.shard_packs")
        r0, r1 = int(bounds[p]), int(bounds[p + 1])
        row_map[p, : r1 - r0] = np.arange(r0, r1, dtype=np.int32)
        blocks.append([
            _block_rows(rp, ci, v, r0, r1,
                        q * cs, min((q + 1) * cs, m.shape[1]), local_cols)
            for q in range(Q)
        ])

    if pack == "ell":
        W = max(1, max((len(c) for prow in blocks for rows in prow
                        for c, _ in rows), default=1))
        col = np.zeros((parts, Q, rows_pp, W), dtype=np.int32)
        val = np.zeros((parts, Q, rows_pp, W), dtype=v.dtype)
        for p in range(parts):
            for q in range(Q):
                for i, (c, vv) in enumerate(blocks[p][q]):
                    col[p, q, i, : len(c)] = c
                    val[p, q, i, : len(c)] = vv
        return ShardSlabs("ell", col, val, None, row_map, bounds, cs,
                          rows_pp, m.n_rows, m.shape[1], m.nnz)

    # flat SELL-C pack: sigma-sort the partition's rows by block length
    # (whole-partition window -> full JDS sort per shard), chunk by C, pad
    # each chunk to its own width, store chunk-column-major.  One shared
    # permutation-aware packer with the local SELL container (formats.py).
    flats: list[list[tuple[np.ndarray, np.ndarray, np.ndarray]]] = []
    L = 1
    for p in range(parts):
        prow = []
        for q in range(Q):
            rows = blocks[p][q]
            lens = [len(c) for c, _ in rows]
            order = sigma_sort_order(lens, sigma=max(1, len(rows)))
            cat = pack_chunks_flat(rows, C, order, rid_fill=rows_pp,
                                   val_dtype=v.dtype)
            L = max(L, len(cat[0]))
            prow.append(cat)
        flats.append(prow)
    col = np.zeros((parts, Q, L), dtype=np.int32)
    val = np.zeros((parts, Q, L), dtype=v.dtype)
    rid = np.full((parts, Q, L), rows_pp, dtype=np.int32)
    for p in range(parts):
        for q in range(Q):
            c, vv, r = flats[p][q]
            col[p, q, : len(c)] = c
            val[p, q, : len(c)] = vv
            rid[p, q, : len(c)] = r
    return ShardSlabs("sell", col, val, rid, row_map, bounds, cs,
                      rows_pp, m.n_rows, m.shape[1], m.nnz)


# ---------------------------------------------------------------------------
# shard_map executors (3 variants x {spmv, spmm})
# ---------------------------------------------------------------------------


def _slab_mult(pack: str, rows_pp: int, backend: str = "xla",
               op: str = "spmv"):
    """One (rows_pp-sized) partial product of a single column slab,
    dispatched through the kernel registry (``slab_ell`` / ``slab_sell``
    entries in ``repro.kernels.slab``).

    ell: 2-D gather + width reduction.  sell: flat gather + segment-sum over
    partition-local row ids (padding rows land in segment ``rows_pp`` and
    are dropped).  ``x`` may be (n,) or (n, K); today's registered builders
    serve both ops, but the executor requests the op it actually runs.
    """
    from ..kernels.slab import slab_mult
    return slab_mult(pack, rows_pp, backend, op=op)


def _row_inverse(blocks: ShardSlabs) -> np.ndarray:
    """Global row -> its (shard, local-row) slot in the flattened output.

    Rows are partitioned, so each is produced by exactly one slot (pad
    slots map to ``n_rows``): undoing the shard layout is an n-element
    *gather*, not a scatter-add (which XLA:CPU lowers serially)."""
    n = blocks.n_rows
    rmap = np.asarray(blocks.row_map).reshape(-1)
    pos = np.nonzero(rmap < n)[0]
    if n and not (np.bincount(rmap[pos], minlength=n) == 1).all():
        raise ValueError("row partition does not cover every row exactly once")
    inv = np.empty(n, dtype=np.int32)
    inv[rmap[pos]] = pos
    return inv


def _device_arrays(blocks: ShardSlabs, mesh: Mesh, axis: str) -> tuple:
    """One device-put of the executor operands ``(col, val, rid, inv)``,
    shared by the SpMV and SpMM executors (and by every variant reusing the
    same packing).  The slabs are sharded over ``axis`` on their leading
    (partition) dimension, so every device holds only its own; the row
    inverse is replicated.  ell ignores row ids; a rank-3 dummy keeps the
    shard_map specs uniform."""
    rid = (blocks.rid if blocks.rid is not None
           else np.zeros((blocks.parts, 1, 1), np.int32))
    slabs = tuple(
        jax.device_put(a, NamedSharding(mesh, P(axis, *([None] * (a.ndim - 1)))))
        for a in (blocks.col, blocks.val, rid))
    return (*slabs, jax.device_put(_row_inverse(blocks), NamedSharding(mesh, P())))


def _make_executor(blocks: ShardSlabs, mesh: Mesh, axis: str, variant: str,
                   multi: bool, backend: str = "xla"):
    """Build the jitted distributed executor ``run(operands, x) -> y`` for
    one variant (``x`` of shape (n,) or, with ``multi``, (n, K)).

    ``operands`` are ``_device_arrays``: the sharded slabs enter the
    program as arguments, never as embedded constants; only x moves per
    call.  ``backend`` picks the registry entry for the inner slab
    multiply (``xla`` is the only entry expressible inside ``shard_map``
    today; ``loop_reference`` exists for parity testing).
    """
    parts = blocks.parts
    pack = blocks.pack
    rows_pp = blocks.rows_pp
    cs = blocks.col_shard
    mult = _slab_mult(pack, rows_pp, backend, op="spmm" if multi else "spmv")
    perm = [(j, (j - 1) % parts) for j in range(parts)]

    def _mark_varying(y):  # the loop accumulator must be device-varying
        return jax.lax.pcast(y, (axis,), to="varying")

    def _slab_at(colQ, valQ, ridQ, src):
        cb = jax.lax.dynamic_index_in_dim(colQ, src, 0, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(valQ, src, 0, keepdims=False)
        rb = jax.lax.dynamic_index_in_dim(ridQ, src, 0, keepdims=False)
        return cb, vb, rb

    if variant == "allgather":
        def local(colb, valb, ridb, xloc):
            xfull = jax.lax.all_gather(xloc, axis, tiled=True)
            y = mult(colb[0, 0], valb[0, 0], ridb[0, 0], xfull)
            return y[None]
    elif variant == "ring":
        def local(colb, valb, ridb, xloc):
            colQ, valQ, ridQ = colb[0], valb[0], ridb[0]
            me = jax.lax.axis_index(axis)

            def body(s, carry):
                y, xs = carry
                cb, vb, rb = _slab_at(colQ, valQ, ridQ, (me + s) % parts)
                y = y + mult(cb, vb, rb, xs)
                xs = jax.lax.ppermute(xs, axis, perm)
                return (y, xs)

            shape = (rows_pp,) if xloc.ndim == 1 else (rows_pp, xloc.shape[1])
            y0 = _mark_varying(jnp.zeros(shape, dtype=valQ.dtype))
            # parts-1 looped steps; the last slab needs no trailing permute
            y, xs = jax.lax.fori_loop(0, parts - 1, body, (y0, xloc))
            cb, vb, rb = _slab_at(colQ, valQ, ridQ, (me + parts - 1) % parts)
            y = y + mult(cb, vb, rb, xs)
            return y[None]
    elif variant == "overlap":
        def local(colb, valb, ridb, xloc):
            colQ, valQ, ridQ = colb[0], valb[0], ridb[0]
            me = jax.lax.axis_index(axis)

            def slab(src, xs):
                return mult(*_slab_at(colQ, valQ, ridQ, src), xs)

            # step 0: issue the permute BEFORE touching the local column
            # block, so the first remote shard is in flight while the only
            # communication-free work runs (Schubert et al.'s overlap)
            xs = xloc
            if parts > 1:
                xs_next = jax.lax.ppermute(xs, axis, perm)
            y = slab(me, xs)
            # unrolled remainder of the ring, permute-first at every step
            for s in range(1, parts):
                xs = xs_next
                if s < parts - 1:
                    xs_next = jax.lax.ppermute(xs, axis, perm)
                y = y + slab((me + s) % parts, xs)
            return y[None]
    else:
        raise ValueError(f"unknown variant {variant!r}")

    slab_rank = 4 if pack == "ell" else 3
    spec_slab = P(axis, *([None] * (slab_rank - 1)))
    f = _shard_map(
        local, mesh=mesh,
        in_specs=(spec_slab, spec_slab, P(axis, None, None),
                  P(axis, None) if multi else P(axis)),
        out_specs=P(axis, None, None) if multi else P(axis, None),
    )

    def run(ops, x: jnp.ndarray) -> jnp.ndarray:
        col, val, rid, inv = ops
        pad = parts * cs - x.shape[0]
        xp = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        yparts = f(col, val, rid, xp)
        return yparts.reshape((-1,) + yparts.shape[2:])[inv]

    return jax.jit(run)


# ---------------------------------------------------------------------------
# traffic accounting (per-SpMV modelled byte movement)
# ---------------------------------------------------------------------------


def slab_traffic_bytes(blocks: ShardSlabs, variant: str, value_bytes: int = 4) -> dict:
    """Modelled bytes per SpMV: matrix stream, collective volume, and the
    peak per-chip x footprint (the quantity the ring/overlap variants cut
    from full-x down to one or two shards).  ``overlap`` double-buffers:
    the held shard and the in-flight permuted shard are alive together, so
    its peak is 2 shards (that concurrency *is* the overlap)."""
    parts = blocks.parts
    idx_bytes = 4 * (2 if blocks.pack == "sell" else 1)  # col (+ rid) streams
    hbm = blocks.stored * (value_bytes + idx_bytes)
    collective = parts * (parts - 1) * blocks.col_shard * value_bytes
    x_shards = {"allgather": parts, "ring": 1, "overlap": min(2, parts)}[variant]
    per_chip_x = x_shards * blocks.col_shard * value_bytes
    return {"hbm_stream": hbm, "collective": collective, "per_chip_x": per_chip_x}


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------


@dataclass
class DistributedSpMVPlan:
    """A compiled distributed SpMV/SpMM: partitioning, per-shard slab
    packing, format selection and the shard_map programs are built once;
    ``plan(x)`` / ``plan.spmm(X)`` replay cached jitted executors.  The
    per-shard slabs live in device memory for the plan's lifetime — the
    paper's NUMA-local first-touch, by construction."""

    variant: str                    # "allgather" | "ring" | "overlap"
    parts: int
    axis: str
    slab_format: str                # committed SPMD slab pack
    balance: str                    # "nnz" | "rows"
    blocks: ShardSlabs
    shard_reports: tuple            # per-partition ShardReport
    kernel: object                  # jitted f(operands, x) -> y
    kernel_mm: object               # jitted f(operands, X) -> Y
    operands: tuple                 # (col, val, rid, inv) on the mesh
    traffic: dict                   # modelled per-SpMV byte movement
    slab_backend: str = "xla"       # registry entry of the inner multiplies

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.spmv(x)

    def _fault_ctx(self, op: str) -> dict:
        return {"op": op, "variant": self.variant, "parts": self.parts,
                "backend": self.slab_backend, "kernel": self.variant}

    def spmv(self, x: jnp.ndarray) -> jnp.ndarray:
        """One distributed SpMV through the cached shard_map executor.

        Args:
            x: input vector of shape (N,); it is padded to the shard grid
                and scattered over the mesh per the plan's variant.

        Returns:
            y = A @ x of shape (M,), gathered back to the caller.
        """
        from ..testing import faults
        if x.shape != (self.blocks.n_cols,):
            raise ValueError(f"x has shape {x.shape}, expected ({self.blocks.n_cols},)")
        spec = faults.fire("dist.spmv", ctx=self._fault_ctx("spmv"))
        y = self.kernel(self.operands, x)
        return faults.poison(y, spec) if spec is not None else y

    def spmm(self, X: jnp.ndarray) -> jnp.ndarray:
        """Multi-vector SpMV: X (N, K) -> Y (M, K), one distributed pass.

        Both the HBM matrix stream *and* the collective x-shard exchange
        are paid once for all K columns — batching amortizes the
        communication too."""
        from ..testing import faults
        if X.ndim != 2 or X.shape[0] != self.blocks.n_cols:
            raise ValueError(f"X has shape {X.shape}, expected ({self.blocks.n_cols}, K)")
        spec = faults.fire("dist.spmm", ctx=self._fault_ctx("spmm"))
        Y = self.kernel_mm(self.operands, X)
        return faults.poison(Y, spec) if spec is not None else Y

    # -- back-compat + introspection ----------------------------------------

    @property
    def strategy(self) -> str:
        """Alias of ``variant`` (pre-plan API name)."""
        return self.variant

    @property
    def imbalance(self) -> float:
        """max/mean stored nnz over shards (1.0 = perfect)."""
        stored = (np.asarray(self.blocks.val) != 0).reshape(self.parts, -1).sum(axis=1)
        return float(stored.max() / max(1.0, stored.mean()))

    @property
    def local_fraction(self) -> float:
        """Fraction of nnz multiplied without communication (what overlap
        can hide the first transfer behind)."""
        tot = max(1, sum(r.nnz for r in self.shard_reports))
        return sum(r.local_nnz for r in self.shard_reports) / tot

    @property
    def report(self) -> PlanReport:
        """A ``core.plan.PlanReport``-shaped summary so plan consumers
        (serving stats, benchmarks) treat local and distributed plans
        uniformly.  Predicted time is the straggler shard's."""
        t = max((r.times[self.slab_format] for r in self.shard_reports),
                default=1e-12)
        nnz = self.blocks.nnz
        flops = 2.0 * nnz
        bytes_streamed = self.traffic["hbm_stream"] + self.traffic["collective"]
        return PlanReport(
            format=f"dist-{self.slab_format}",
            shape=(self.blocks.n_rows, self.blocks.n_cols),
            nnz=nnz,
            kernel=self.variant,
            chunk_block=None, width_block=None, vmem_bytes=None,
            balance_bytes_per_flop=bytes_streamed / max(1.0, flops),
            predicted_gflops=flops / t / 1e9,
            predicted_time_s=t,
            bound="memory",
        )

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (f"DistributedSpMVPlan({self.variant}, parts={self.parts}, "
                f"slab={self.slab_format}, imbalance={self.imbalance:.3f})")


def _sell_to_coo(s):
    """SELL -> COO without densifying: unpack each chunk's (w, C) slab,
    keep stored non-zeros of real (non-pad) rows."""
    from .formats import COO

    cp, cw = np.asarray(s.chunk_ptr), np.asarray(s.chunk_width)
    ci, v, perm = np.asarray(s.col_idx), np.asarray(s.val), np.asarray(s.perm)
    rows_out, cols_out, vals_out = [], [], []
    for c in range(s.n_chunks):
        w = int(cw[c])
        block_c = ci[cp[c]:cp[c + 1]].reshape(w, s.C)
        block_v = v[cp[c]:cp[c + 1]].reshape(w, s.C)
        rows = perm[c * s.C:(c + 1) * s.C]
        keep = (block_v != 0) & (rows[None, :] < s.shape[0])
        rows_out.append(np.broadcast_to(rows[None, :], block_v.shape)[keep])
        cols_out.append(block_c[keep])
        vals_out.append(block_v[keep])
    cat = lambda xs, dt: np.concatenate(xs) if xs else np.zeros(0, dt)  # noqa: E731
    return COO(cat(rows_out, np.int32).astype(np.int32),
               cat(cols_out, np.int32).astype(np.int32),
               cat(vals_out, v.dtype), s.shape)


def _as_csr(matrix) -> CSR:
    """Partitioning is row_ptr-driven, so plans compile from CSR; other
    containers are converted once (sparse-to-sparse, never via a dense
    intermediate) and the view cached on them."""
    from .formats import COO, ELL

    if isinstance(matrix, CSR):
        return matrix
    cached = getattr(matrix, "_csr_view", None)
    if cached is None:
        if isinstance(matrix, COO):
            cached = CSR.from_coo(matrix)
        elif isinstance(matrix, ELL):
            col, val = np.asarray(matrix.col_idx), np.asarray(matrix.val)
            rows = np.broadcast_to(
                np.arange(matrix.shape[0], dtype=np.int32)[:, None], val.shape)
            keep = val != 0
            cached = CSR.from_coo(COO(rows[keep], col[keep].astype(np.int32),
                                      val[keep], matrix.shape))
        elif hasattr(matrix, "chunk_ptr"):  # SELL
            cached = CSR.from_coo(_sell_to_coo(matrix))
        else:
            raise TypeError(f"no distributed plan for {type(matrix).__name__}")
        object.__setattr__(matrix, "_csr_view", cached)
    return cached


def _resolve_slab_backend(backend: str) -> str:
    """Normalize the distributed ``backend=`` to a slab registry entry.

    The inner multiplies run inside ``shard_map``, where only the XLA slab
    entries are expressible today — ``auto``/``xla``/``ref`` (and the
    Pallas names, which degrade gracefully like the local plan layer does
    for formats without a Pallas kernel) all resolve to ``xla``;
    ``loop_reference`` selects the slab loop oracles for parity debugging.
    """
    if backend in ("auto", "xla", "ref", "pallas", "pallas_interpret"):
        return "xla"
    if backend == "loop_reference":
        return backend
    raise ValueError(f"unknown backend {backend!r}")


def compile_distributed_spmv_plan(
    m,
    mesh: Mesh | None = None,
    *,
    variant: str = "overlap",
    balance: str = "nnz",
    slab_format: str = "auto",
    axis: str = "data",
    C: int = 8,
    config=None,
    **plan_kw,
) -> DistributedSpMVPlan:
    """Partition ``m`` over the mesh and return a memoized distributed plan.

    ``m`` is CSR (other containers are converted through a cached CSR
    view).  ``slab_format="auto"`` lets the roofline choose between the
    stacked packings per shard (``plan_shard_formats``) and commits to the
    one that minimizes the straggler's predicted time; pass
    ``"ell"``/``"sell"`` to force.  ``config`` (a ``core.planconfig.
    PlanConfig``) carries ``chip`` / ``am`` / ``backend`` — the backend
    selects the registry entry for the inner slab multiplies (see
    ``_resolve_slab_backend``); bare ``chip=`` / ``am=`` / ``backend=``
    kwargs remain as deprecated aliases.  The slab packer sigma-sorts each
    partition in full (the per-shard JDS sort), so ``config.sigma`` does
    not apply here.  Compiling twice with the same key returns the same
    object — each shard is packed exactly once per key (``pack_stats``
    counts).
    """
    from .planconfig import coerce_config
    cfg = coerce_config(config, plan_kw, api="compile_distributed_spmv_plan")
    chip, am, backend = cfg.chip, cfg.am, cfg.backend
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; expected one of {VARIANTS}")
    be = _resolve_slab_backend(backend)
    m = _as_csr(m)
    if am is None:  # dtype-honest default: charge the stored value bytes
        am = PM.access_model_for(m, chip)
    mesh = mesh if mesh is not None else make_mesh_1d(axis)
    parts = int(mesh.shape[axis])
    dev_ids = tuple(int(d.id) for d in np.asarray(mesh.devices).flat)
    key = (variant, balance, slab_format, axis, parts, C, chip.name, am,
           dev_ids, be)
    cache = getattr(m, "_dist_plans", None)
    if cache is None:
        cache = {}
        object.__setattr__(m, "_dist_plans", cache)
    plan = cache.get(key)
    if plan is None:
        plan = _compile(m, mesh, variant, balance, slab_format, axis, C,
                        chip, am, be)
        cache[key] = plan
    return plan


def _compile(m, mesh, variant, balance, slab_format, axis, C, chip, am,
             backend: str = "xla"):
    parts = int(mesh.shape[axis])
    bounds = (nnz_balanced_partition(m, parts) if balance == "nnz"
              else row_balanced_partition(m.n_rows, parts))
    reports = plan_shard_formats(m, bounds, C=C, am=am, chip=chip)
    pack = select_slab_format(reports) if slab_format == "auto" else slab_format
    # ring and overlap share one packing + device upload (identical layout);
    # the slab cache lives next to the plan memo on the matrix container
    cache = getattr(m, "_dist_plans")
    local_cols = variant != "allgather"
    skey = ("slabs", balance, pack, local_cols, C, parts)
    blocks = cache.get(skey)
    if blocks is None:
        blocks = cache[skey] = pack_shard_slabs(
            m, parts, balance=balance, pack=pack, local_cols=local_cols, C=C,
            bounds=bounds)
    dkey = (skey, axis, tuple(int(d.id) for d in np.asarray(mesh.devices).flat))
    arrays = cache.get(dkey)
    if arrays is None:
        arrays = cache[dkey] = _device_arrays(blocks, mesh, axis)
    run = _make_executor(blocks, mesh, axis, variant, multi=False,
                         backend=backend)
    run_mm = _make_executor(blocks, mesh, axis, variant, multi=True,
                            backend=backend)
    traffic = slab_traffic_bytes(blocks, variant,
                                 np.dtype(np.asarray(m.val).dtype).itemsize)
    return DistributedSpMVPlan(variant, parts, axis, pack, balance, blocks,
                               tuple(reports), run, run_mm, arrays, traffic,
                               slab_backend=backend)


def plan_all_variants(m: CSR, mesh: Mesh | None = None, **kw) -> dict:
    """Compile all three variants over the same mesh — the distributed
    analogue of ``plan.plan_all_formats`` (benchmarks compare them)."""
    return {v: compile_distributed_spmv_plan(m, mesh, variant=v, **kw)
            for v in VARIANTS}
