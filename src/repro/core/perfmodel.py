"""The paper's predictive performance model, generalized and TPU-calibrated.

The paper's central claim (Sec. 1): a useful model must be *predictive* for
SpMVM performance "for a given matrix on the basis of its sparsity pattern,
and give a hint to the respective optimal storage scheme".  Its ingredients:

* **algorithmic balance** B = bytes moved per Flop for a (format, pattern)
  pair — CRS = 10 B/F and JDS = 18 B/F at fp64/int32 (Sec. 2), blocked JDS
  approaching CRS balance;
* **line-granularity waste** — at stride k, a whole cache line is moved per
  touched element and only 1/k of it is used (Sec. 4.1, penalty #2);
* **index traffic** — +4 B/element for the indexing array (penalty #1,
  "overhead of around 50 % for ISADD");
* the bandwidth roofline  perf = min(peak, BW / B).

TPU adaptation: the "cache line" becomes the HBM/VMEM access granularity of
a gather (one (8,128) or (1,128) tile row per distinct element in the worst
case — parameterized as ``line_elems``); the result-vector write-allocate of
JDS becomes the repeated HBM round-trip of the accumulator when a jagged
diagonal does not fit VMEM.  Everything is parameterized by byte widths so
the paper's exact fp64 numbers are reproduced in the tests.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import numpy as np

from ..utils.hw import ChipSpec, TPU_V5E


@dataclass(frozen=True)
class AccessModel:
    """Byte-accounting parameters for one SpMV execution."""

    value_bytes: int = 8      # fp64 in the paper; 4 (fp32) / 2 (bf16) on TPU
    index_bytes: int = 4
    line_elems: int = 8       # elements per memory-access granule (64B line / fp64)
    invec_waste: float = 1.0  # mean granule fraction wasted multiplier (>=1)
    invec_reuse: float = 1.0  # <1 if invec elements are re-served from cache/VMEM

    def invec_bytes_per_access(self) -> float:
        return self.value_bytes * self.invec_waste * self.invec_reuse


def waste_from_stride(mean_stride: float, line_elems: int) -> float:
    """Paper penalty #2: at stride k only 1/k of each granule is useful.

    waste = min(k, line_elems): stride 1 -> 1.0 (dense), stride >= line
    -> line_elems (whole granule per element).
    """
    return float(np.clip(mean_stride, 1.0, line_elems))


# ---------------------------------------------------------------------------
# per-format balance (bytes per Flop); 2 Flops per stored element
# ---------------------------------------------------------------------------


def balance_csr(am: AccessModel, nnz_per_row: float = np.inf) -> float:
    """CRS: val + col_idx + invec per element; result kept in register,
    written once per row (amortized over nnz_per_row)."""
    per_elem = am.value_bytes + am.index_bytes + am.invec_bytes_per_access()
    per_elem += 2 * am.value_bytes / max(1.0, nnz_per_row)  # resvec ld+st per row
    return per_elem / 2.0


def balance_jds(am: AccessModel) -> float:
    """JDS: like CRS plus a resvec load+store per element (paper: 18 B/F)."""
    per_elem = (
        am.value_bytes + am.index_bytes + am.invec_bytes_per_access()
        + 2 * am.value_bytes
    )
    return per_elem / 2.0


def balance_blocked_jds(am: AccessModel, rows_per_block: int, nnz_per_row: float) -> float:
    """NBJDS/RBJDS/SELL: resvec tile cached across the block's diagonals.

    The resvec round-trip happens once per block instead of once per
    element: amortization factor = block nnz / block rows = nnz_per_row.
    With full amortization this recovers CRS balance (paper Sec. 2: "it
    eventually becomes equal to CRS balance").
    """
    per_elem = am.value_bytes + am.index_bytes + am.invec_bytes_per_access()
    per_elem += 2 * am.value_bytes / max(1.0, nnz_per_row)
    return per_elem / 2.0


def balance_ell(am: AccessModel, pad_ratio: float, nnz_per_row: float = np.inf) -> float:
    """ELL streams padding too: all streamed terms scale by pad_ratio
    (= padded elements / nnz >= 1)."""
    return balance_csr(am, nnz_per_row) * pad_ratio


def balance_sell(am: AccessModel, pad_ratio: float, nnz_per_row: float) -> float:
    return balance_blocked_jds(am, 0, nnz_per_row) * pad_ratio


def flat_sell_access_model(am: AccessModel, overhead: float = 1.0) -> AccessModel:
    """Flat SELL-C streams one extra row id per stored element (the
    segment-sum's index stream) on top of the column index.  Shared by the
    distributed slab planner and the registry cost hooks — this doubling
    used to be constructed inline in ``distributed_plan``.

    ``overhead`` scales the whole per-element stream cost by the measured
    execution deficit of the segment-sum lowering (``sell_flat_overhead``);
    1.0 keeps the purely physical byte count."""
    return replace(am, value_bytes=am.value_bytes * overhead,
                   index_bytes=2 * am.index_bytes * overhead)


def balance_slab(pack: str, am: AccessModel, pad_ratio: float,
                 nnz_per_row: float) -> float:
    """Balance of one distributed slab pack: padded-ELL pays the partition's
    padding ratio; flat SELL pays only per-chunk padding but adds the
    row-index stream of a segment-sum."""
    if pack == "ell":
        return balance_ell(am, pad_ratio, nnz_per_row)
    if pack == "sell":
        return balance_sell(flat_sell_access_model(am), pad_ratio, nnz_per_row)
    raise ValueError(f"unknown slab format {pack!r}")


def balance_bsr(am: AccessModel, block_shape: tuple[int, int], fill_ratio: float) -> float:
    """BSR: index traffic amortized over bm*bn, invec reuse factor bm inside a
    block (each x element feeds bm rows).  ``fill_ratio`` = stored elements /
    true nnz (explicit zeros streamed and multiplied).  Balance is per
    *useful* Flop, so streamed terms scale by fill_ratio."""
    bm, bn = block_shape
    per_stored = (
        am.value_bytes
        + am.index_bytes / (bm * bn)
        + am.value_bytes * am.invec_reuse / bm  # stride-1 inside the block: no waste
    )
    per_stored += 2 * am.value_bytes / bn  # resvec tile ld+st per block row
    return per_stored * fill_ratio / 2.0


def balance_dia(am: AccessModel, n_diags: int, occupancy: float = 1.0,
                invec_cached: bool = True) -> float:
    """DIA: zero index traffic, stride-1 shifted invec reads.  Streams one
    val + one invec element per *stored* slot; unoccupied slots (zeros) are
    streamed too -> divide by occupancy.  If the invec working set stays in
    cache/VMEM across diagonals, its traffic amortizes over n_diags."""
    invec = am.value_bytes * (1.0 / n_diags if invec_cached and n_diags > 0 else 1.0)
    per_stored = am.value_bytes + invec + 2 * am.value_bytes / max(1, n_diags)
    return per_stored / (occupancy * 2.0)


def balance_matrix_free(am: AccessModel, n_stored: int, n_rows: int,
                        nnz: int) -> float:
    """Matrix-free generated operator: *zero* index traffic and zero value
    traffic for generated diagonals -- indices are recomputed from the row
    id and constant values fold into the instruction stream.  What still
    moves: the stored DIA-style lanes (``n_stored * n_rows`` values, padding
    zeros included), x streamed once (stride-1 shifted windows reuse the
    cached working set across diagonals), and the result read+written."""
    streamed = am.value_bytes * (n_stored * n_rows + 3 * n_rows)
    return streamed / (2.0 * max(1, nnz))


# paper-calibrated presets -------------------------------------------------

PAPER_FP64 = AccessModel(value_bytes=8, index_bytes=4, line_elems=8,
                         invec_waste=1.0, invec_reuse=1.0)
TPU_FP32 = AccessModel(value_bytes=4, index_bytes=4, line_elems=32,
                       invec_waste=1.0, invec_reuse=1.0)
TPU_BF16 = AccessModel(value_bytes=2, index_bytes=4, line_elems=64,
                       invec_waste=1.0, invec_reuse=1.0)


def value_bytes_of(fmt_obj) -> int:
    """itemsize of the container's *stored* value array (hybrid: SELL part).

    The per-group fp32 scale of int8/fp8 containers is ignored: one scale
    per row/chunk/block/diagonal amortizes to well under a byte per stored
    element for any matrix the balance model is meaningful on.
    """
    from . import formats as F

    if isinstance(fmt_obj, F.HybridDIA):
        fmt_obj = fmt_obj.rest
    if isinstance(fmt_obj, F.MatrixFreeOperator):
        # generated-only operators store nothing; byte widths still follow
        # the declared storage precision (x / y / stored-lane streams)
        return int(np.dtype(F.VALUE_DTYPES.get(fmt_obj.value_dtype,
                                               np.float32)).itemsize)
    return int(np.dtype(np.asarray(F.container_values(fmt_obj)).dtype).itemsize)


def access_model_for(fmt_obj, chip: ChipSpec | None = None,
                     base: AccessModel | None = None) -> AccessModel:
    """An ``AccessModel`` whose ``value_bytes`` matches the container's
    stored dtype (the fix for charging every container 4-byte values).

    ``line_elems`` keeps the 128-byte access granule of the TPU presets
    (f32 -> ``TPU_FP32`` exactly, bf16 -> ``TPU_BF16`` exactly), so f32
    paths are byte-identical to the historical default.  ``chip`` is
    accepted for signature stability; the byte widths are chip-independent
    today.
    """
    del chip  # granule size is uniform across the supported chips
    vb = value_bytes_of(fmt_obj)
    b = base if base is not None else TPU_FP32
    return replace(b, value_bytes=vb, line_elems=max(1, 128 // vb))


# ---------------------------------------------------------------------------
# roofline predictor
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Prediction:
    format: str
    balance_bytes_per_flop: float
    flops: float                 # useful Flops of one SpMV
    bytes_streamed: float
    time_s: float
    gflops: float
    cycles_per_element: float    # paper Fig 2/6 y-axis (at chip clock)
    bound: str                   # "memory" | "compute"


def predict(
    fmt: str,
    balance: float,
    nnz: int,
    chip: ChipSpec = TPU_V5E,
    clock_hz: float | None = None,
) -> Prediction:
    """perf = min(peak, BW / balance); times for one SpMV of 2*nnz Flops.

    Args:
        fmt: format label carried into the Prediction (reporting only).
        balance: bytes per Flop from one of the ``balance_*`` functions.
        nnz: stored elements of the operation (2 Flops each).
        chip: bandwidth/peak parameters of the target machine.
        clock_hz: clock for the cycles-per-element column (default: 1 GHz,
            i.e. the column reads as cycles-per-GHz).

    Returns:
        A ``Prediction`` with the modelled time, GFlop/s and the binding
        resource ("memory" | "compute").
    """
    flops = 2.0 * nnz
    bytes_streamed = balance * flops
    t_mem = bytes_streamed / chip.hbm_bytes_per_s
    t_cmp = flops / chip.peak_flops_fp32
    # floor for degenerate empty operands (e.g. the SELL remainder of a
    # hybrid split that promoted every diagonal): 0 flops in >0 time
    time_s = max(t_mem, t_cmp, 1e-30)
    clock = clock_hz if clock_hz is not None else 1e9  # report per-GHz cycles
    return Prediction(
        format=fmt,
        balance_bytes_per_flop=balance,
        flops=flops,
        bytes_streamed=bytes_streamed,
        time_s=time_s,
        gflops=flops / time_s / 1e9,
        cycles_per_element=time_s / max(1, nnz) * clock,
        bound="memory" if t_mem >= t_cmp else "compute",
    )


# ---------------------------------------------------------------------------
# format advisor (the paper's "hint to the respective optimal storage scheme")
# ---------------------------------------------------------------------------


def ell_pad_ratio(row_lengths: np.ndarray) -> float:
    """ELL padding ratio (stored / nnz) from the row-length profile:
    every row is padded to the longest row's length."""
    ml = row_lengths.max() if row_lengths.size else 0
    mean = row_lengths.mean() if row_lengths.size else 1
    return float(ml / max(1e-9, mean))


#: registry backends whose SELL execution streams the *flat* chunk-local
#: layout (sum_c w_c * C elements).  The XLA formulation instead consumes
#: the globally padded (nc, W_max, C) views — W_max = the longest row — so
#: its matrix stream inflates by the global padding ratio.  This is the
#: BENCH_PR4 honest miss: the power-law matrix measured far below the
#: flat-SELL model under XLA precisely because of these extra bytes.
FLAT_SELL_BACKENDS = ("pallas", "pallas_interpret", "loop_reference")

#: measured execution overhead of the flat (segment-sum) XLA SELL
#: formulation relative to the padded gather/reduce, per chip family, as a
#: multiplier on its per-element stream cost.  XLA:CPU lowers
#: ``segment_sum`` + the perm-scatter to serial scatter-adds, so the flat
#: form runs far below the padded form's streaming efficiency even though
#: it moves fewer bytes (measured this box: holstein padded 294us vs flat
#: 3016us at a 1.3x byte advantage).  Calibrated so the flat regime's
#: effective efficiency matches the PR9 measured tier on the CI host
#: (0.29 / 4.5 ~= 0.065, the implied flat-sell efficiency on powerlaw).
SELL_FLAT_OVERHEAD = {"cpu": 4.5, "tpu": 1.0}


def sell_flat_overhead(family: str | None = None) -> float:
    """Flat-formulation execution-overhead factor for ``family``; ``None``
    resolves the family the kernels will actually execute on (the runtime
    platform, not a modeled chip): ``cpu``, or ``tpu`` for a TPU kind in
    ``utils.hw.DEVICE_KINDS`` — any other device is an error."""
    if family is None:
        import jax

        if jax.default_backend() == "cpu":
            family = "cpu"
        else:
            from ..utils.hw import chip_for_device
            family = chip_family(chip_for_device())
    return float(SELL_FLAT_OVERHEAD.get(family, 1.0))


def sell_xla_uses_flat(m, family: str | None = None) -> bool:
    """Does the XLA SELL entry pick its *flat* (segment-sum) formulation
    for this container?

    The XLA entry has two formulations: the historical padded-view
    gather/reduce over ``(nc, W_max, C)`` — whose matrix stream is blind
    to sigma-sorting because every chunk pays the longest row — and a flat
    segment-sum over the chunk-local layout (``sum_c w_c * C`` elements)
    that streams one extra row id per stored element.  The flat form wins
    when its total matrix bytes, charged at the segment-sum's measured
    execution overhead, are smaller::

        flat * (vb + 2*ib) * overhead  <  padded * (vb + ib)

    At f32 on CPU (overhead 4.5) that needs padded/flat > 6.75: regular
    and mildly irregular matrices keep the einsum-friendly padded form,
    and only genuinely irregular patterns — power-law rows, where
    sigma-sorting pays and padding is catastrophic — switch.  The
    predicate depends only on the container and the runtime platform, so
    the model and the compiled kernel agree wherever both run.
    """
    flat = int(np.asarray(m.val).shape[0])
    cw = np.asarray(m.chunk_width)
    wmax = int(cw.max()) if cw.size else 1
    padded = int(m.n_chunks * wmax * m.C)
    am = access_model_for(m)
    vb, ib = am.value_bytes, am.index_bytes
    return flat * (vb + 2 * ib) * sell_flat_overhead(family) \
        < padded * (vb + ib)


def sell_streamed_elements(m, backend: str = "xla") -> int:
    """Stored elements one SpMV actually streams for a concrete ``SELL``
    container under ``backend`` (flat chunk-local vs globally padded; the
    XLA entry streams flat when ``sell_xla_uses_flat`` says so)."""
    flat = int(np.asarray(m.val).shape[0])
    if backend in FLAT_SELL_BACKENDS:
        return flat
    if backend == "xla" and sell_xla_uses_flat(m):
        return flat
    cw = np.asarray(m.chunk_width)
    wmax = int(cw.max()) if cw.size else 1
    return int(m.n_chunks * wmax * m.C)


def sell_stream_am(m, am: AccessModel, backend: str = "xla") -> AccessModel:
    """The access model the executed SELL regime streams with: the flat
    XLA formulation adds the segment-sum's row-id stream (2x index bytes)
    charged at its measured execution overhead; the padded XLA form and
    the Pallas kernels stream physically."""
    if backend == "xla" and sell_xla_uses_flat(m):
        return flat_sell_access_model(am, sell_flat_overhead())
    return am


def sell_padded_view_ratio(row_lengths: np.ndarray, C: int) -> float:
    """Padding ratio (streamed / nnz) of the globally padded SELL views the
    XLA backend consumes: every chunk is padded to the longest row."""
    n = len(row_lengths)
    if n == 0:
        return 1.0
    n_pad = -(-n // C) * C
    wmax = int(row_lengths.max())
    return n_pad * wmax / max(1, int(row_lengths.sum()))


def sell_pad_ratio(row_lengths: np.ndarray, C: int, sigma: int) -> float:
    """Exact padding ratio of SELL-C-sigma for the given row lengths."""
    n = len(row_lengths)
    if n == 0:
        return 1.0
    lens = row_lengths.astype(np.int64).copy()
    out = np.empty_like(lens)
    for s in range(0, n, max(1, sigma)):
        e = min(s + sigma, n)
        out[s:e] = np.sort(lens[s:e])[::-1]
    n_pad = -(-n // C) * C
    padded = np.zeros(n_pad, dtype=np.int64)
    padded[:n] = out
    widths = padded.reshape(-1, C).max(axis=1)
    stored = int((widths * C).sum())
    return stored / max(1, int(lens.sum()))


def sell_sigma_candidates(n_rows: int, C: int = 8) -> tuple:
    """Candidate SELL sorting windows for a matrix of ``n_rows`` rows:
    identity (1), chunk-local (C), two cache-friendly windows (64 and the
    repo default), and the full JDS sort (n) — clipped to [1, n_rows] and
    deduplicated, ascending."""
    from . import formats as F

    n = max(1, int(n_rows))
    cands = {1, int(C), 64, F.DEFAULT_SELL_SIGMA, n}
    return tuple(sorted({max(1, min(n, s)) for s in cands}))


def select_sell_sigma(row_lengths, C: int = 8,
                      candidates=None) -> tuple[int, float]:
    """Autotune the SELL sorting window from row lengths alone.

    Scores each candidate sigma by its exact flat padding ratio
    (``sell_pad_ratio``) and returns ``(sigma, pad_ratio)`` of the
    minimum; ties go to the *smaller* window (less reordering — cheaper
    pack, better locality of the inverse scatter).  Pattern-only, so the
    TuneDB signature stays chunk-geometry-independent.
    """
    lens = np.asarray(row_lengths)
    n = len(lens)
    if candidates is None:
        candidates = sell_sigma_candidates(n, C)
    best_s, best_r = 1, None
    for s in candidates:            # ascending: ties keep the smaller sigma
        r = sell_pad_ratio(lens, C, int(s))
        if best_r is None or r < best_r - 1e-12:
            best_s, best_r = int(s), r
    return best_s, float(best_r if best_r is not None else 1.0)


def advise(
    stats: dict,
    row_lengths: np.ndarray,
    am: AccessModel = TPU_FP32,
    C: int = 8,
    sigma: int | None = None,
    chip: ChipSpec = TPU_V5E,
) -> dict:
    """Rank formats by predicted SpMV time from pattern statistics alone.

    ``stats`` comes from ``formats.matrix_stats``; no conversion is done.
    Returns {format: Prediction}, plus '_best'.
    """
    nnz = int(stats["nnz"])
    npr = float(stats["nnz_per_row_mean"])
    mean_stride = max(1.0, float(stats["mean_inner_stride"]))
    am_eff = replace(am, invec_waste=waste_from_stride(mean_stride, am.line_elems))
    sig = sigma if sigma is not None else len(row_lengths)
    preds = {
        "csr": predict("csr", balance_csr(am_eff, npr), nnz, chip),
        "jds": predict("jds", balance_jds(am_eff), nnz, chip),
        "ell": predict("ell", balance_ell(am_eff, ell_pad_ratio(row_lengths), npr), nnz, chip),
        "sell": predict("sell", balance_sell(am_eff, sell_pad_ratio(row_lengths, C, sig), npr), nnz, chip),
    }
    # hybrid DIA+SELL if the diagonal fraction is substantial
    frac_diag = float(stats.get("frac_nnz_top12_diags", 0.0))
    if frac_diag > 0.3:
        n_d = 12
        b_dia = balance_dia(am_eff, n_d, occupancy=0.9)
        rest_pad = sell_pad_ratio(row_lengths, C, sig)  # approx: same distribution
        b_rest = balance_sell(am_eff, rest_pad, npr * (1 - frac_diag))
        b_mix = frac_diag * b_dia + (1 - frac_diag) * b_rest
        preds["hybrid"] = predict("hybrid", b_mix, nnz, chip)
    best = min(preds, key=lambda k: preds[k].time_s)
    out = dict(preds)
    out["_best"] = best
    return out


def balance_of(fmt_obj, am: AccessModel | None = None, backend: str = "xla") -> float:
    """Algorithmic balance (bytes/Flop) for a *concrete* converted matrix —
    the post-conversion analogue of ``advise``'s pattern-only estimates.
    Pad/fill ratios are exact because the container is in hand.

    ``backend`` selects the stream-byte regime where formats differ per
    executor — today that is SELL (flat chunk-local layout for the Pallas
    kernels and the loop oracle vs globally padded views for XLA; see
    ``sell_streamed_elements``).

    ``am=None`` derives the byte widths from the container's stored value
    dtype (``access_model_for``) — an f64 container is charged 8-byte
    values, a bf16 one 2-byte values."""
    from . import formats as F

    if am is None:
        am = access_model_for(fmt_obj)
    if isinstance(fmt_obj, F.CSR):
        npr = fmt_obj.nnz / max(1, fmt_obj.shape[0])
        return balance_csr(am, npr)
    if isinstance(fmt_obj, F.COO):
        # like CRS but with an explicit row index per element and a
        # scattered (not register-held) result accumulation
        per_elem = (am.value_bytes + 2 * am.index_bytes
                    + am.invec_bytes_per_access() + 2 * am.value_bytes)
        return per_elem / 2.0
    if isinstance(fmt_obj, (F.ELL,)):
        stored = int(np.prod(np.asarray(fmt_obj.val).shape))
        npr = fmt_obj.nnz / max(1, fmt_obj.shape[0])
        return balance_ell(am, stored / max(1, fmt_obj.nnz), npr)
    if isinstance(fmt_obj, F.JDS):
        return balance_jds(am)
    if isinstance(fmt_obj, F.SELL):
        stored = sell_streamed_elements(fmt_obj, backend)
        npr = fmt_obj.nnz / max(1, fmt_obj.shape[0])
        return balance_sell(sell_stream_am(fmt_obj, am, backend),
                            stored / max(1, fmt_obj.nnz), npr)
    if isinstance(fmt_obj, F.BSR):
        return balance_bsr(am, fmt_obj.block_shape, fill_ratio=1.0)
    if isinstance(fmt_obj, F.DIA):
        stored = int(np.prod(np.asarray(fmt_obj.data).shape))
        nd = max(1, int(np.asarray(fmt_obj.offsets).shape[0]))
        occ = fmt_obj.nnz / max(1, stored)
        return balance_dia(am, nd, occupancy=max(1e-3, occ))
    if isinstance(fmt_obj, F.MatrixFreeOperator):
        return balance_matrix_free(am, fmt_obj.n_stored, fmt_obj.shape[0],
                                   fmt_obj.nnz)
    if isinstance(fmt_obj, F.HybridDIA):
        n_dia, n_rest = fmt_obj.dia.nnz, fmt_obj.rest.nnz
        total = max(1, n_dia + n_rest)
        return (n_dia * balance_of(fmt_obj.dia, am)
                + n_rest * balance_of(fmt_obj.rest, am, backend)) / total
    raise TypeError(type(fmt_obj))


# ---------------------------------------------------------------------------
# concrete-container format selection (the corpus-validated selector)
# ---------------------------------------------------------------------------

#: Fraction of the chip's streaming bandwidth each vectorized formulation
#: actually achieves, relative to the byte model, per chip family.  The
#: paper's pure balance ranking assumes every kernel streams at the same
#: rate — true for its serial CPU loops, false for gather/segment-sum
#: formulations on a compiler backend.  The ``cpu`` table is calibrated
#: from the measured BENCH_PR1..PR3 trajectory (effective GB/s =
#: gflops x balance on the CPU runner: ELL 2.7, SELL 0.77, hybrid 0.51,
#: JDS 0.23, CSR 0.14 — ELL's regular take+einsum sustains ~20x CSR's
#: per-element segment-sum, and measured DIA lands near hybrid, see
#: ``benchmarks/corpus_sweep.py``).  The ``tpu`` table follows the paper's
#: structure (DIA's stride-1 shifted reads and BSR's dense MXU tiles near
#: streaming rate; the Pallas SELL kernel well above the flat XLA one).
#: ``corpus_sweep`` measures the residual prediction error per matrix —
#: the feedback loop that keeps these numbers honest.
EXEC_EFFICIENCY = {
    "tpu": {
        "csr": 0.10, "coo": 0.08, "jds": 0.15, "ell": 0.90,
        "sell": 0.60, "hybrid": 0.50, "dia": 0.80, "bsr": 0.80,
        "matrix_free": 0.85,
    },
    "cpu": {
        # csr/hybrid recalibrated against the PR9 measured tier on the CI
        # host.  sell 0.29 describes the *padded-view* formulation; the
        # flat (segment-sum) regime's much lower execution efficiency is
        # charged separately as SELL_FLAT_OVERHEAD on its stream bytes
        # (0.29 / 4.5 ~= 0.065, the implied flat efficiency on powerlaw),
        # so one efficiency entry covers both formulations.
        # matrix_free calibrated from the PR10 sweep: the shifted-read
        # chain sustains ~0.8-1.0 of the measured STREAM bandwidth on its
        # tiny byte stream (indices and generated values never move).
        "csr": 0.08, "coo": 0.05, "jds": 0.085, "ell": 1.00,
        "sell": 0.29, "hybrid": 0.065, "dia": 0.19, "bsr": 0.90,
        "matrix_free": 0.90,
    },
}

#: chip-name substrings that resolve to the ``cpu`` efficiency table (the
#: paper's x86 systems plus the calibrated host runner).
CPU_CHIP_MARKERS = ("cpu", "host", "woodcrest", "shanghai", "nehalem", "x86")

#: family an *unknown accelerator* resolves to.  The cpu table encodes the
#: measured gather/segment-sum penalties of a compiler CPU backend —
#: applying it to an unrecognized accelerator (a future GPU/TPU name) is a
#: silent miscalibration; the structural ``tpu`` table is the safe default
#: for anything that is not recognizably a CPU.
DEFAULT_CHIP_FAMILY = "tpu"


def chip_family(chip: ChipSpec | None) -> str:
    """Resolve a chip to its ``EXEC_EFFICIENCY`` family (never raises).

    ``"tpu"`` anywhere in the name wins; the known CPU markers (including
    the paper's x86 systems, whose names contain no "cpu") map to
    ``"cpu"``; everything else — unknown accelerators — pins to
    ``DEFAULT_CHIP_FAMILY`` instead of a KeyError or a silent cpu
    miscalibration.  The tuning DB uses the same resolution for its
    entry keys (``core.tunedb``).
    """
    name = chip.name.lower() if chip is not None else ""
    if "tpu" in name:
        return "tpu"
    if any(marker in name for marker in CPU_CHIP_MARKERS):
        return "cpu"
    return DEFAULT_CHIP_FAMILY


def exec_efficiency(chip: ChipSpec) -> dict:
    """The formulation-efficiency table matching a chip family."""
    return EXEC_EFFICIENCY[chip_family(chip)]


@dataclass(frozen=True)
class FormatChoice:
    """Outcome of ``select_format``: the pick plus the curve behind it.

    Attributes:
        format: chosen format name (a ``formats.convert`` key).
        predicted_time_s: {format: efficiency-adjusted roofline seconds}
            over every candidate that was considered (warm picks report
            the *measured* seconds the tuning DB recorded instead).
        convert_kwargs: kwargs to pass to ``formats.convert`` for the
            chosen format (chunk/block geometry).
        stats: the ``matrix_stats`` snapshot the decision used.
        source: ``"model"`` (cold path: roofline ranking) or
            ``"measured"`` (warm path: a fresh tuning-DB entry decided).
    """

    format: str
    predicted_time_s: dict
    convert_kwargs: dict
    stats: dict
    source: str = "model"


def predict_exec(fmt: str, balance: float, nnz: int, chip: ChipSpec = TPU_V5E,
                 efficiency: dict | None = None) -> Prediction:
    """``predict`` with the formulation's achievable-bandwidth derating."""
    eff = (efficiency if efficiency is not None
           else exec_efficiency(chip)).get(fmt, 1.0)
    derated = replace(chip, hbm_bytes_per_s=chip.hbm_bytes_per_s * eff)
    return predict(fmt, balance, nnz, chip=derated)


def resolve_stream_backend(backend: str = "auto") -> str:
    """The stream-byte regime the default executor would use: the XLA
    formulations — on TPU too, where the SELL Pallas kernels (the only
    format whose streamed bytes differ per backend) do not lower and the
    registry refuses them."""
    return "xla" if backend == "auto" else backend


def select_format(
    m,
    *,
    am: AccessModel | None = None,
    chip: ChipSpec = TPU_V5E,
    C: int = 8,
    sigma: int | None = None,
    allowed=None,
    efficiency: dict | None = None,
    max_dia_diags: int = 256,
    bsr_block: tuple[int, int] = (8, 128),
    backend: str = "auto",
    tuning=None,
) -> FormatChoice:
    """Pick the storage format for a concrete CSR/COO container.

    The paper's "hint to the respective optimal storage scheme", upgraded
    from pattern statistics to the container in hand: pad ratios are exact,
    diagonal occupancy and BSR block fill are counted instead of estimated,
    and every candidate's balance is pushed through the execution-aware
    roofline (``predict_exec``) so the ranking reflects what the vectorized
    kernels actually sustain, not just bytes.

    Unlike ``advise`` (the paper-faithful serial model), no cache-line
    waste term is applied here: the irregular-gather cost of each
    vectorized formulation is already folded into the measured
    ``EXEC_EFFICIENCY`` calibration, and applying both double-counts it
    (e.g. a 5-point stencil's stride-47 jumps would predict ELL ~8x worse
    than the fused gather actually measures).

    Args:
        m: a ``CSR`` (or ``COO``, converted internally).  Any other
            container returns the identity choice — its format was already
            decided upstream.
        am / chip: access model and roofline parameters.
        C / sigma: SELL chunk geometry used for padding estimates and
            carried into ``convert_kwargs``.  ``sigma=None`` autotunes the
            sorting window per matrix (``select_sell_sigma``); the chosen
            value is recorded in the sell/hybrid ``convert_kwargs``.
        allowed: optional iterable restricting the candidate formats.
        efficiency: override of ``EXEC_EFFICIENCY``.
        max_dia_diags: DIA is only considered when the matrix populates at
            most this many distinct (sub)diagonals.
        bsr_block: BSR is only considered when the shape divides this
            block and the populated blocks are reasonably full.
        backend: stream-byte regime for backend-dependent formats
            (``"auto"`` = the executor this host would pick).  The XLA
            SELL formulation streams globally padded views, so under
            ``backend="xla"`` the SELL candidate is charged
            ``sell_padded_view_ratio`` instead of the flat chunk-local
            ratio — this closes the BENCH_PR4 power-law misprediction.
        tuning: a ``core.tunedb.TuneDB`` (or a path to one) holding
            measured winners.  A fresh entry for this matrix decides the
            pick directly (the **warm path**, ``choice.source ==
            "measured"``); otherwise the DB's re-fit ``EXEC_EFFICIENCY``
            factors refine the roofline ranking when no explicit
            ``efficiency`` override was given.  ``None`` (default) is the
            cold path — bitwise-identical to the model-only behavior.

    Returns:
        A ``FormatChoice``; compile the pick with
        ``SpMVPlan.compile(convert(m, choice.format, **choice.convert_kwargs))``
        or simply ``SpMVPlan.compile(m, format="auto")``.
    """
    from . import formats as F

    if isinstance(m, F.COO):
        m = F.CSR.from_coo(m)
    if not isinstance(m, F.CSR):
        name = {v: k for k, v in F.FORMATS.items()}.get(type(m))
        if name is None:
            raise TypeError(f"select_format: unsupported container {type(m).__name__}")
        return FormatChoice(name, {}, {}, {})

    if tuning is not None:
        from . import tunedb as _tunedb
        db = _tunedb.open_db(tuning)
        hit = (db.lookup_format(m, chip=chip, allowed=allowed)
               if db is not None else None)
        if hit is not None:
            fmt, kw, times = hit
            return FormatChoice(fmt, times, kw, F.matrix_stats(m),
                                source="measured")
        if db is not None and efficiency is None:
            efficiency = db.efficiency_for(chip)

    if am is None:
        am = access_model_for(m)
    stats = F.matrix_stats(m)
    lens = m.row_lengths()
    nnz = max(1, m.nnz)
    npr = float(stats["nnz_per_row_mean"])
    # score the packing that will actually execute.  sigma=None autotunes
    # the sorting window from the row-length profile (select_sell_sigma);
    # the chosen sigma is carried into convert_kwargs so the conversion
    # packs exactly what was scored.
    if sigma is None:
        sig, flat_ratio = select_sell_sigma(lens, C)
    else:
        sig = max(1, min(m.shape[0], int(sigma)))
        flat_ratio = sell_pad_ratio(lens, C, sig)
    be = resolve_stream_backend(backend)
    if be in FLAT_SELL_BACKENDS:
        sell_ratio, am_sell = flat_ratio, am
    else:
        # mirror of sell_xla_uses_flat at pattern level: the XLA entry
        # streams the flat layout (plus a row-id per element, charged at
        # the segment-sum's measured execution overhead) when that costs
        # less than the globally padded views
        padded_ratio = sell_padded_view_ratio(lens, C)
        vb, ib = am.value_bytes, am.index_bytes
        ovh = sell_flat_overhead(chip_family(chip))
        if flat_ratio * (vb + 2 * ib) * ovh < padded_ratio * (vb + ib):
            sell_ratio, am_sell = flat_ratio, flat_sell_access_model(am, ovh)
        else:
            sell_ratio, am_sell = padded_ratio, am

    balances = {
        "csr": balance_csr(am, npr),
        "jds": balance_jds(am),
        "ell": balance_ell(am, ell_pad_ratio(lens), npr),
        "sell": balance_sell(am_sell, sell_ratio, npr),
    }
    kwargs = {
        "csr": {}, "jds": {},
        "ell": {},
        "sell": {"C": C, "sigma": int(sig)},
    }

    coo = m.to_coo()
    offs = np.asarray(coo.cols, np.int64) - np.asarray(coo.rows, np.int64)
    uniq_offs, off_counts = np.unique(offs, return_counts=True)
    n_diags = len(uniq_offs)

    # hybrid: split the well-occupied diagonals off, SELL the rest
    frac_diag = float(stats.get("frac_nnz_top12_diags", 0.0))
    if frac_diag > 0.3:
        b_dia = balance_dia(am, 12, occupancy=0.9)
        b_rest = balance_sell(am_sell, sell_ratio, npr * (1 - frac_diag))
        balances["hybrid"] = frac_diag * b_dia + (1 - frac_diag) * b_rest
        kwargs["hybrid"] = {"C": C, "sigma": int(sig)}

    # pure DIA: only when the diagonal profile is genuinely narrow AND the
    # kept diagonals are reasonably full — below ~20% occupancy the dense
    # diagonal stream moves >5x zeros and regularity cannot pay for it
    if 0 < n_diags <= max_dia_diags:
        stored = n_diags * min(m.shape)
        occ = nnz / max(1, stored)
        if occ >= 0.2:
            balances["dia"] = balance_dia(am, n_diags, occupancy=occ)
            kwargs["dia"] = {}

    # matrix-free: the generated-operator candidate.  Exact (cached)
    # structure detection gates it; a qualifying operator streams zero
    # index bytes and zero value bytes for its generated diagonals, so on
    # stencil/banded rows it undercuts every materialized format.  Stored
    # lanes must be reasonably occupied (same 20% floor as DIA) or the
    # dense-lane zeros eat the win.
    if 0 < n_diags <= max_dia_diags:
        mf = F.detect_matrix_free(m, max_diags=max_dia_diags)
        if mf is not None and (
                mf.n_stored == 0
                or mf.stored_nnz / (mf.n_stored * m.shape[0]) >= 0.2):
            balances["matrix_free"] = balance_matrix_free(
                am, mf.n_stored, m.shape[0], nnz)
            kwargs["matrix_free"] = {}

    # BSR: only when the shape tiles exactly and populated blocks are full
    bm, bn = bsr_block
    if m.shape[0] % bm == 0 and m.shape[1] % bn == 0 and nnz > 0:
        rows_np = np.asarray(coo.rows, np.int64)
        cols_np = np.asarray(coo.cols, np.int64)
        blocks = np.unique(rows_np // bm * (m.shape[1] // bn) + cols_np // bn)
        fill = nnz / (len(blocks) * bm * bn)
        if fill >= 0.25:
            balances["bsr"] = balance_bsr(am, bsr_block, fill_ratio=1.0 / fill)
            kwargs["bsr"] = {"block_shape": bsr_block}

    if allowed is not None:
        allowed = set(allowed)
        balances = {k: v for k, v in balances.items() if k in allowed}
        if not balances:
            raise ValueError(f"no candidate formats left after allowed={sorted(allowed)}")
    preds = {fmt: predict_exec(fmt, b, nnz, chip=chip, efficiency=efficiency).time_s
             for fmt, b in balances.items()}
    best = min(preds, key=preds.get)
    return FormatChoice(best, preds, kwargs[best], stats)


def fit_efficiency_from_db(db, *, chip: ChipSpec | None = None,
                           family: str | None = None,
                           clamp: tuple = (0.01, 1.5)) -> dict:
    """Re-fit the ``EXEC_EFFICIENCY`` factors from tuning-DB measurements.

    For every recorded candidate, the achieved efficiency is the ratio of
    the *efficiency-1* roofline prediction (pure byte model) to the
    measured time::

        eff = t_model_eff1_s / t_measured_s

    (a kernel measuring 2x slower than the byte model achieved 0.5 of the
    modelled bandwidth).  Per format, the fitted factor is the geometric
    mean of the achieved efficiencies across matrices and backends —
    robust to the order-of-magnitude spread between regular and
    irregular patterns — clamped to ``clamp`` so one degenerate timing
    cannot zero a format out of contention.

    Only entries of the requested chip family contribute (timings from
    another family are a different machine).  Formats with no
    measurements keep their hand-calibrated default, so the fitted table
    is always complete.

    Args:
        db: a ``core.tunedb.TuneDB`` populated by ``backend_sweep --tune``.
        chip / family: which ``EXEC_EFFICIENCY`` family to fit (pass one;
            ``family`` wins; default: the family of ``TPU_V5E``).
        clamp: (lo, hi) bounds on each fitted factor.

    Returns:
        {format: efficiency} — the default table overlaid with the fit.
    """
    fam = family if family is not None else chip_family(chip or TPU_V5E)
    ratios: dict[str, list] = {}
    for entry in db.entries.values():
        if entry.get("chip_family") != fam:
            continue
        for c in entry.get("candidates", ()):
            t, t1 = c.get("t_measured_s"), c.get("t_model_eff1_s")
            if t and t1 and t > 0 and t1 > 0:
                ratios.setdefault(c["format"], []).append(t1 / t)
    fitted = dict(EXEC_EFFICIENCY.get(fam, EXEC_EFFICIENCY[DEFAULT_CHIP_FAMILY]))
    lo, hi = clamp
    for fmt, rs in ratios.items():
        geo = float(np.exp(np.mean(np.log(rs))))
        fitted[fmt] = float(np.clip(geo, lo, hi))
    return fitted


# ---------------------------------------------------------------------------
# Pallas block autotuning (model-driven, no on-device search)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BlockChoice:
    """Selected (chunk_block, width_block) for the SELL Pallas kernel."""

    chunk_block: int
    width_block: int
    width_padded: int     # W after padding to a width_block multiple
    vmem_bytes: int       # working-set claim of the choice
    fits_vmem: bool


def _divisors_desc(n: int, cap: int) -> list[int]:
    return [d for d in range(min(n, cap), 0, -1) if n % d == 0]


def select_pallas_blocks(
    n_chunks: int,
    width: int,
    C: int,
    n_cols: int,
    *,
    value_bytes: int = 4,
    index_bytes: int = 4,
    chip: ChipSpec = TPU_V5E,
    max_chunk_block: int = 64,
) -> BlockChoice:
    """Pick (chunk_block, width_block) for ``sell_spmv_arrays`` from the
    byte model alone: maximize the streamed slab (pipeline amortization)
    subject to the VMEM working set fitting the kernels' budget
    (``utils.hw.vmem_fits``).  Prefers a full-width block (one pass over
    the output tile, no revisits) when it fits.

    Deterministic and host-only — the "autotuning" is the paper's predictive
    model applied to the kernel's BlockSpec instead of an on-device sweep.
    """
    from ..kernels.sell_spmv import vmem_bytes as _vmem_claim  # deferred: no cycle

    from ..utils.hw import vmem_fits

    width = max(1, width)
    n_chunks = max(1, n_chunks)
    # width_block candidates: powers of two up to width (padding W up to a
    # multiple costs streamed zeros, so only consider wb <= next_pow2(width))
    wbs = []
    wb = 1
    while wb < width:
        wb *= 2
    wbs.append(wb)  # full width in a single pass
    while wb > 1:
        wb //= 2
        wbs.append(wb)
    best: BlockChoice | None = None
    for wb in wbs:                       # descending: full-width first
        w_pad = -(-width // wb) * wb
        for cb in _divisors_desc(n_chunks, max_chunk_block):
            claim = _vmem_claim(cb, wb, C, n_cols, value_bytes, index_bytes, value_bytes)
            if not vmem_fits(claim, chip):
                continue
            cand = BlockChoice(cb, wb, w_pad, int(claim), True)
            if best is None or (cand.chunk_block * cand.width_block
                                > best.chunk_block * best.width_block):
                best = cand
        if best is not None and best.width_block == wb:
            break  # larger slabs only shrink from here; full-width preferred
    if best is None:  # nothing fits (x alone blows VMEM): caller must fall back
        wb = wbs[-1]
        claim = _vmem_claim(1, wb, C, n_cols, value_bytes, index_bytes, value_bytes)
        best = BlockChoice(1, wb, -(-width // wb) * wb, int(claim), False)
    return best


# ---------------------------------------------------------------------------
# SpMM batching model (micro-batched serving)
# ---------------------------------------------------------------------------


def matrix_stream_bytes(fmt_obj, am: AccessModel | None = None,
                        backend: str = "xla") -> float:
    """Bytes of the *matrix* stream alone (values + indices, padding included).

    This is the traffic component that batching amortizes: an SpMM with k
    right-hand sides streams the matrix once, not k times.  Vector traffic
    (input gathers + result write-back) still scales with k.

    Args:
        fmt_obj: a concrete converted container from ``core.formats``.
        am: byte-width parameterization of the access model.
        backend: stream-byte regime (see ``balance_of``); affects SELL.

    Returns:
        Modelled bytes of one pass over the stored matrix.  ``am=None``
        derives byte widths from the stored value dtype.
    """
    from . import formats as F

    if am is None:
        am = access_model_for(fmt_obj)
    if isinstance(fmt_obj, (F.CSR, F.JDS)):
        return float((am.value_bytes + am.index_bytes) * fmt_obj.nnz)
    if isinstance(fmt_obj, F.COO):
        return float((am.value_bytes + 2 * am.index_bytes) * fmt_obj.nnz)
    if isinstance(fmt_obj, F.ELL):
        stored = int(np.prod(np.asarray(fmt_obj.val).shape))
        return float((am.value_bytes + am.index_bytes) * stored)
    if isinstance(fmt_obj, F.SELL):
        stored = sell_streamed_elements(fmt_obj, backend)
        am_s = sell_stream_am(fmt_obj, am, backend)
        return float((am_s.value_bytes + am_s.index_bytes) * stored)
    if isinstance(fmt_obj, F.BSR):
        bm, bn = fmt_obj.block_shape
        return float((am.value_bytes * bm * bn + am.index_bytes) * fmt_obj.n_blocks)
    if isinstance(fmt_obj, F.DIA):
        nd, n = np.asarray(fmt_obj.data).shape
        return float(am.value_bytes * nd * n)
    if isinstance(fmt_obj, F.MatrixFreeOperator):
        # only the stored DIA-style lanes move; generated diagonals are
        # zero-byte (index and value both recomputed in-kernel)
        return float(am.value_bytes * fmt_obj.n_stored * fmt_obj.shape[0])
    if isinstance(fmt_obj, F.HybridDIA):
        return (matrix_stream_bytes(fmt_obj.dia, am)
                + matrix_stream_bytes(fmt_obj.rest, am, backend))
    raise TypeError(type(fmt_obj))


def spmm_balance_of(fmt_obj, k: int, am: AccessModel | None = None,
                    backend: str = "xla") -> float:
    """Algorithmic balance (bytes per Flop) of an SpMM at batch width ``k``.

    One SpMM of width k does ``2 * nnz * k`` Flops while streaming the matrix
    once and the vector traffic k times:

        balance(k) = (matrix_bytes + k * vector_bytes) / (2 * nnz * k)

    ``k == 1`` reproduces ``balance_of`` exactly; as k grows, balance falls
    toward ``vector_bytes / (2 * nnz)`` — the paper's memory-bound ceiling
    lifts by up to the matrix-to-vector traffic ratio.

    Args:
        fmt_obj: a concrete converted container from ``core.formats``.
        k: batch width (number of simultaneous right-hand sides), >= 1.
        am: byte-width parameterization of the access model.

    Returns:
        Modelled bytes moved per useful Flop at width k.
    """
    k = max(1, int(k))
    if am is None:
        am = access_model_for(fmt_obj)
    total1 = balance_of(fmt_obj, am, backend) * 2.0 * fmt_obj.nnz  # one SpMV
    mat = matrix_stream_bytes(fmt_obj, am, backend)
    vec = max(0.0, total1 - mat)                           # invec + resvec share
    return (mat + k * vec) / (2.0 * fmt_obj.nnz * k)


@dataclass(frozen=True)
class BatchWidthChoice:
    """Outcome of ``select_batch_width``: the policy width + the curve behind it.

    Attributes:
        width: selected batch width (the serving layer's flush width).
        widths: candidate widths that were evaluated (powers of two).
        throughput: {k: predicted queries/s} over the candidates.
        balance: {k: predicted bytes/Flop} over the candidates.
        saturation: throughput(width) / max throughput over candidates —
            how close the chosen width sits to the model's asymptote.
    """

    width: int
    widths: tuple
    throughput: dict
    balance: dict
    saturation: float


def select_batch_width(
    fmt_obj,
    *,
    am: AccessModel | None = None,
    chip: ChipSpec = TPU_V5E,
    k_max: int = 64,
    efficiency: float = 0.9,
    backend: str = "xla",
) -> BatchWidthChoice:
    """Pick the serving batch width from the SpMM roofline.

    Predicted throughput at width k is ``k / time(SpMM_k)`` with
    ``time = max(bytes / BW, flops / peak)``.  Throughput rises while the
    matrix stream dominates and saturates once vector traffic (or the
    compute roof) takes over; the policy picks the *smallest* power-of-two
    width reaching ``efficiency`` of the best candidate's throughput —
    larger batches would only add queueing latency for no modelled gain.

    Args:
        fmt_obj: a concrete converted container from ``core.formats``.
        am: byte-width parameterization of the access model.
        chip: roofline parameters (HBM bandwidth, peak Flop/s).
        k_max: largest candidate width (rounded up to a power of two).
        efficiency: fraction of the asymptotic throughput to settle for.
        backend: stream-byte regime of the executor that will run the
            flushes (see ``balance_of``) — the width knee moves with the
            matrix-stream size, so a flat-streaming Pallas SELL SpMM must
            not be policied with padded XLA bytes.

    Returns:
        A ``BatchWidthChoice``; ``choice.width`` is the flush width.
    """
    if am is None:
        am = access_model_for(fmt_obj)
    ks = []
    k = 1
    while k < k_max:
        ks.append(k)
        k *= 2
    ks.append(k)  # first power of two >= k_max
    qps, bal = {}, {}
    for k in ks:
        b = spmm_balance_of(fmt_obj, k, am, backend)
        pred = predict("spmm", b, fmt_obj.nnz * k, chip=chip)
        bal[k] = b
        qps[k] = k / pred.time_s
    best = max(qps.values())
    width = next(k for k in ks if qps[k] >= efficiency * best)
    return BatchWidthChoice(width=width, widths=tuple(ks), throughput=qps,
                            balance=bal, saturation=qps[width] / best)


def spmv_streamed_bytes(fmt_obj, am: AccessModel | None = None,
                        backend: str = "xla",
                        generated_indices: bool = False) -> float:
    """Model-side byte count for a *concrete* converted matrix (used to
    validate predictions against measured/compiled traffic).  ``am=None``
    derives byte widths from the container's stored value dtype.

    ``generated_indices=True`` is the zero-index-bytes counterfactual: the
    same container's stream with every index charged at 0 bytes, i.e. what
    a kernel that recomputes ``col = row + offset`` in-registers would
    move.  The gap against the default accounting is exactly the traffic a
    ``MatrixFreeOperator`` deletes (a ``MatrixFreeOperator`` operand
    already streams zero index bytes either way)."""
    from . import formats as F

    if am is None:
        am = access_model_for(fmt_obj)
    if generated_indices:
        am = replace(am, index_bytes=0)
    if isinstance(fmt_obj, F.CSR):
        return (am.value_bytes + am.index_bytes + am.invec_bytes_per_access()) * fmt_obj.nnz \
            + 2 * am.value_bytes * fmt_obj.shape[0]
    if isinstance(fmt_obj, F.ELL):
        stored = int(np.prod(np.asarray(fmt_obj.val).shape))
        return (am.value_bytes + am.index_bytes + am.invec_bytes_per_access()) * stored \
            + 2 * am.value_bytes * fmt_obj.shape[0]
    if isinstance(fmt_obj, F.JDS):
        return (am.value_bytes + am.index_bytes + am.invec_bytes_per_access()
                + 2 * am.value_bytes) * fmt_obj.nnz
    if isinstance(fmt_obj, F.SELL):
        stored = sell_streamed_elements(fmt_obj, backend)
        am_s = sell_stream_am(fmt_obj, am, backend)
        return (am_s.value_bytes + am_s.index_bytes
                + am_s.invec_bytes_per_access()) * stored \
            + 2 * am.value_bytes * fmt_obj.shape[0]
    if isinstance(fmt_obj, F.BSR):
        bm, bn = fmt_obj.block_shape
        nb = fmt_obj.n_blocks
        return (am.value_bytes * bm * bn + am.index_bytes + am.value_bytes * bn
                + 2 * am.value_bytes * bm) * nb
    if isinstance(fmt_obj, F.DIA):
        nd, n = np.asarray(fmt_obj.data).shape
        return am.value_bytes * nd * n + am.value_bytes * n + 2 * am.value_bytes * n
    if isinstance(fmt_obj, F.MatrixFreeOperator):
        n = fmt_obj.shape[0]
        return (am.value_bytes * fmt_obj.n_stored * n   # stored lanes
                + am.value_bytes * n                    # x streamed once
                + 2 * am.value_bytes * n)               # y read + written
    if isinstance(fmt_obj, F.HybridDIA):
        return (spmv_streamed_bytes(fmt_obj.dia, am)
                + spmv_streamed_bytes(fmt_obj.rest, am, backend))
    raise TypeError(type(fmt_obj))
