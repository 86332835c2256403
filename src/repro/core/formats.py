"""Sparse matrix storage formats from the paper, adapted to TPU tiling.

The paper (Schubert/Hager/Fehske 2009) studies CRS (=CSR) and JDS plus the
blocked refinements NBJDS / RBJDS / NUJDS / SOJDS.  On TPU the natural
incarnations are:

  CSR        -- reference / host format (paper's CRS).
  ELL        -- padded row-major-jagged format; the degenerate JDS where all
                rows are padded to the max length.  Dense 2D operands.
  JDS        -- the paper's jagged-diagonals storage (row permutation +
                column-major jagged diagonals).
  SELL       -- SELL-C-sigma, the modern descendant of the paper's blocked
                NBJDS (chunk height C = TPU tile rows, sorting window sigma
                = the paper's row-permutation scope).  RBJDS's "store block
                contiguously" is exactly SELL's chunk-local layout, and
                SOJDS's stride sorting maps to in-chunk column sorting.
  BSR        -- block CSR with MXU-aligned dense blocks (the paper's "dense
                subblocks ... can be exploited" remark, made first-class).
  DIA+SELL   -- hybrid split: dense secondary diagonals (60% of nnz in the
                Holstein-Hubbard matrix) stored stride-1, remainder in SELL.

All containers are frozen dataclasses of numpy/jnp arrays so they can be
passed through jit boundaries as pytrees.  Construction happens host-side in
numpy (format conversion is a preprocessing step, exactly as in the paper);
the SpMV compute consumes the arrays on device.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import ml_dtypes
import numpy as np

try:  # register pytrees if jax present (always true in this repo)
    import jax
except Exception:  # pragma: no cover
    jax = None

Array = Any

#: one default sorting window for SELL-C-sigma, shared by ``SELL.from_csr``,
#: ``corpus.corpus_stats``, ``corpus.MatrixSpec`` and the perfmodel's format
#: selector -- the advisor must score the packing that actually executes.
DEFAULT_SELL_SIGMA = 256

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _pytree_dataclass(cls):
    """Register a dataclass whose array fields are leaves and whose metadata
    fields (ints/tuples, listed in ``_static``) are aux data."""
    static = set(getattr(cls, "_static", ()))
    fields = [f.name for f in dataclasses.fields(cls)]
    dyn = [f for f in fields if f not in static]
    stat = [f for f in fields if f in static]

    def flatten(obj):
        return [getattr(obj, f) for f in dyn], tuple(getattr(obj, f) for f in stat)

    def unflatten(aux, children):
        kwargs = dict(zip(dyn, children))
        kwargs.update(dict(zip(stat, aux)))
        return cls(**kwargs)

    if jax is not None:
        jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


def _as_np(a, dtype=None):
    return np.asarray(a, dtype=dtype)


def sigma_sort_order(lens, sigma: int) -> np.ndarray:
    """The SELL-C-sigma row permutation: a stable descending-length argsort
    within consecutive windows of ``sigma`` rows.

    This is the one sigma-sort in the repo -- ``SELL.from_csr`` (local
    containers) and ``distributed_plan.pack_shard_slabs`` (per-partition
    slab packs, which sort the whole partition: ``sigma = len(lens)``) both
    route through it.  ``sigma = 1`` is the identity permutation;
    ``sigma >= len(lens)`` reproduces the full JDS sort.
    """
    lens = np.asarray(lens, dtype=np.int64)
    n = int(lens.shape[0])
    sigma = max(1, int(sigma))
    order = np.arange(n, dtype=np.int32)
    if sigma == 1:
        return order
    for s in range(0, n, sigma):
        e = min(s + sigma, n)
        order[s:e] = np.argsort(-lens[s:e], kind="stable").astype(np.int32) + s
    return order


def pack_chunks_flat(rows, C: int, order=None, rid_fill: int | None = None,
                     val_dtype=None):
    """Flat SELL-C pack of ragged rows into chunk-column-major slabs.

    ``rows`` is a list of ``(col_idx, val)`` pairs (one per row, ragged);
    ``order`` a row permutation (default identity).  Rows are consumed in
    permuted order, cut into chunks of ``C``, each chunk padded to its own
    max length and stored column-major ``(w, C)``; all-empty chunks are
    skipped entirely (they stream zero bytes).  Returns flat 1-D
    ``(col, val, rid)`` arrays where ``rid`` carries each element's
    *pre-permutation* row index and padding elements carry ``rid_fill``
    (default ``len(rows)``) -- exactly what a segment-sum consumer drops.
    """
    n = len(rows)
    if order is None:
        order = np.arange(n, dtype=np.int32)
    if rid_fill is None:
        rid_fill = n
    if val_dtype is None:
        val_dtype = rows[0][1].dtype if n else np.float32
    k = np.array([len(c) for c, _ in rows], dtype=np.int64)
    fc, fv, fr = [], [], []
    for c0 in range(0, n, C):
        chunk = order[c0:c0 + C]
        w = int(k[chunk].max()) if len(chunk) else 0
        if w == 0:
            continue
        ccol = np.zeros((w, C), dtype=np.int32)
        cval = np.zeros((w, C), dtype=val_dtype)
        crid = np.full((w, C), rid_fill, dtype=np.int32)
        for j, i in enumerate(chunk):
            c, vv = rows[i]
            ccol[: len(c), j] = c
            cval[: len(c), j] = vv
            crid[: len(c), j] = i
        fc.append(ccol.ravel())
        fv.append(cval.ravel())
        fr.append(crid.ravel())
    return (np.concatenate(fc) if fc else np.zeros(0, np.int32),
            np.concatenate(fv) if fv else np.zeros(0, val_dtype),
            np.concatenate(fr) if fr else np.zeros(0, np.int32))


# ---------------------------------------------------------------------------
# value dtypes: storage precision is orthogonal to the sparsity format
# ---------------------------------------------------------------------------

#: canonical name -> numpy dtype of every supported value-storage precision.
#: SpMV is bandwidth-bound (paper Sec. 2-3), so value bytes are the lever:
#: bf16/f16 halve the value stream, fp8/int8 quarter it.  Kernels always
#: multiply-accumulate in >= f32 regardless of storage dtype.
VALUE_DTYPES = {
    "f64": np.float64,
    "f32": np.float32,
    "bf16": ml_dtypes.bfloat16,
    "f16": np.float16,
    "fp8_e4m3": ml_dtypes.float8_e4m3fn,
    "int8": np.int8,
}

#: dtypes that need a per-group fp32 scale stored alongside ``val``
#: (symmetric quantization; the others are plain casts).
_QMAX = {"int8": 127.0, "fp8_e4m3": 448.0}  # max representable magnitude
QUANTIZED_DTYPES = tuple(_QMAX)


def value_dtype_name(dtype) -> str:
    """Canonical name ("f32", "int8", ...) of a numpy/jax value dtype."""
    dt = np.dtype(dtype)
    for name, d in VALUE_DTYPES.items():
        if dt == np.dtype(d):
            return name
    return dt.name


def container_values(obj) -> Array:
    """The stored value array of any container (val / vals / blocks / data)."""
    if isinstance(obj, MatrixFreeOperator):
        if obj.data is None:
            raise TypeError(
                "MatrixFreeOperator with fully generated values stores no "
                "value array")
        return obj.data
    for attr in ("val", "vals", "blocks", "data"):
        if hasattr(obj, attr):
            return getattr(obj, attr)
    raise TypeError(f"{type(obj).__name__} has no value array")


def container_value_dtype(obj) -> str:
    """Canonical value-dtype name of a container (hybrid: the SELL part)."""
    if isinstance(obj, HybridDIA):
        obj = obj.rest
    if isinstance(obj, MatrixFreeOperator):
        return obj.value_dtype
    return value_dtype_name(np.asarray(container_values(obj)).dtype)


def _group_scales(amax: np.ndarray, value_dtype: str) -> np.ndarray:
    """fp32 scale per group from per-group |v| maxima; all-zero groups get
    scale 1.0 so quantize/dequantize round-trips them to exact zeros."""
    qmax = _QMAX[value_dtype]
    return np.where(amax > 0, amax / qmax, 1.0).astype(np.float32)


def _quantize_flat(v: np.ndarray, group_ids: np.ndarray, n_groups: int,
                   value_dtype: str) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric per-group quantization of a flat value array."""
    amax = np.zeros(n_groups, np.float64)
    if v.size:
        np.maximum.at(amax, group_ids, np.abs(v.astype(np.float64)))
    scale = _group_scales(amax, value_dtype)
    qv = v.astype(np.float64) / scale[group_ids] if v.size else v.astype(np.float64)
    if value_dtype == "int8":
        q = np.clip(np.rint(qv), -127, 127).astype(np.int8)
    else:
        q = qv.astype(VALUE_DTYPES[value_dtype])
    return q, scale


def _quantize_axis0(v: np.ndarray, value_dtype: str) -> tuple[np.ndarray, np.ndarray]:
    """Per-leading-axis-group quantization (ELL rows, BSR blocks, DIA diags)."""
    n = v.shape[0]
    flat = np.abs(v.astype(np.float64)).reshape(n, -1)
    amax = flat.max(axis=1) if flat.size else np.zeros(n)
    scale = _group_scales(amax, value_dtype)
    bshape = (n,) + (1,) * (v.ndim - 1)
    qv = v.astype(np.float64) / scale.reshape(bshape)
    if value_dtype == "int8":
        q = np.clip(np.rint(qv), -127, 127).astype(np.int8)
    else:
        q = qv.astype(VALUE_DTYPES[value_dtype])
    return q, scale


def _flat_group_ids(obj) -> tuple[np.ndarray, int]:
    """(group id per stored element, n_groups) for flat-value containers."""
    if isinstance(obj, CSR):
        lens = obj.row_lengths()
        return np.repeat(np.arange(obj.n_rows), lens), obj.n_rows
    if isinstance(obj, COO):
        return _as_np(obj.rows).astype(np.int64), obj.shape[0]
    if isinstance(obj, JDS):
        # group = *permuted* row: jagged diagonal d holds rows 0..n_active-1
        segs = [np.arange(L) for L in obj.diag_lengths()]
        ids = np.concatenate(segs) if segs else np.zeros(0, np.int64)
        return ids, obj.shape[0]
    if isinstance(obj, SELL):
        cp = _as_np(obj.chunk_ptr)
        return np.repeat(np.arange(obj.n_chunks), np.diff(cp)), obj.n_chunks
    raise TypeError(f"no flat grouping for {type(obj).__name__}")


def dequantize(obj):
    """Undo ``with_value_dtype``: an f32-valued, scale-free copy of ``obj``.

    For float storage dtypes this is a plain upcast; for int8/fp8 the
    per-group scale is folded back into the values.
    """
    if isinstance(obj, HybridDIA):
        return HybridDIA(dequantize(obj.dia), dequantize(obj.rest), obj.shape)
    v = np.asarray(container_values(obj), dtype=None)
    scale = getattr(obj, "scale", None)
    if scale is None:
        vf = v.astype(np.float32) if v.dtype != np.float64 else v
    elif isinstance(obj, (ELL, BSR, DIA)):
        bshape = (v.shape[0],) + (1,) * (v.ndim - 1)
        vf = v.astype(np.float32) * _as_np(scale).reshape(bshape)
    else:
        ids, _ = _flat_group_ids(obj)
        vf = v.astype(np.float32) * _as_np(scale)[ids]
    return _replace_values(obj, vf, None)


def _replace_values(obj, new_values, new_scale):
    """Same container, new value array (+ scale); preserves everything else."""
    if isinstance(obj, COO):
        return COO(obj.rows, obj.cols, new_values, obj.shape, new_scale)
    if isinstance(obj, CSR):
        return CSR(obj.row_ptr, obj.col_idx, new_values, obj.shape, new_scale)
    if isinstance(obj, ELL):
        return ELL(obj.col_idx, new_values, obj.shape, obj.nnz, new_scale)
    if isinstance(obj, JDS):
        return JDS(obj.jd_ptr, obj.col_idx, new_values, obj.perm, obj.shape, new_scale)
    if isinstance(obj, SELL):
        return SELL(obj.chunk_ptr, obj.chunk_width, obj.col_idx, new_values,
                    obj.perm, obj.shape, obj.C, obj.sigma, obj.nnz, new_scale)
    if isinstance(obj, BSR):
        return BSR(obj.block_row_ptr, obj.block_col_idx, new_values, obj.shape,
                   obj.block_shape, new_scale)
    if isinstance(obj, DIA):
        return DIA(obj.offsets, new_values, obj.shape, new_scale)
    raise TypeError(f"cannot replace values on {type(obj).__name__}")


def _require_unquantized(obj, where: str):
    """Refuse quantized sources in structural conversions: the per-group
    scale layout (row/chunk/block/diagonal) does not survive the reordering
    a conversion performs, so codes would silently lose their scales."""
    if getattr(obj, "scale", None) is not None:
        raise TypeError(
            f"{where}: source is quantized (scale is set) and its scale "
            "groups would not survive the conversion -- dequantize() first, "
            "or use convert(m, fmt, value_dtype=...) which re-quantizes in "
            "the target format's own group layout")


def _require_materialized(obj, where: str):
    """Refuse ``MatrixFreeOperator`` sources in structural conversions: the
    operator carries a pattern *descriptor*, not index arrays, so there is
    nothing for a repacking converter to consume.  ``materialize(op)`` is
    the one sanctioned escape hatch back to explicit-index CSR."""
    if isinstance(obj, MatrixFreeOperator):
        raise TypeError(
            f"{where}: source is a MatrixFreeOperator (a pattern descriptor, "
            "not materialized index arrays) -- call materialize(op) to get "
            "an explicit CSR first")


def with_value_dtype(obj, value_dtype: str):
    """A copy of ``obj`` storing its values in ``value_dtype``.

    f64/f32/bf16/f16 are plain casts (``scale`` stays None).  int8 and
    fp8_e4m3 store symmetrically quantized values plus an fp32 ``scale``
    per group -- row for CSR/COO/ELL, permuted row for JDS, chunk for
    SELL, block for BSR, diagonal for DIA -- chosen so kernels can apply
    the scale to the *reduced* output instead of per stored element.
    Kernels accumulate in >= f32 regardless of the storage dtype.
    """
    if value_dtype not in VALUE_DTYPES:
        raise ValueError(
            f"value_dtype={value_dtype!r}; expected one of {tuple(VALUE_DTYPES)}")
    if isinstance(obj, HybridDIA):
        return HybridDIA(with_value_dtype(obj.dia, value_dtype),
                         with_value_dtype(obj.rest, value_dtype), obj.shape)
    if isinstance(obj, MatrixFreeOperator):
        if value_dtype in _QMAX:
            raise TypeError(
                "with_value_dtype: MatrixFreeOperator stores generated values "
                f"as exact scalars; quantized storage ({value_dtype!r}) has no "
                "per-group scale home -- materialize() first and quantize the "
                "explicit CSR instead")
        data = (obj.data if obj.data is None
                else _as_np(obj.data).astype(VALUE_DTYPES[value_dtype]))
        return dataclasses.replace(obj, data=data, value_dtype=value_dtype)
    if getattr(obj, "scale", None) is not None:
        obj = dequantize(obj)  # re-quantize from the dequantized values
    v = np.asarray(container_values(obj))
    if value_dtype not in _QMAX:
        return _replace_values(obj, v.astype(VALUE_DTYPES[value_dtype]), None)
    if isinstance(obj, (ELL, BSR, DIA)):
        q, scale = _quantize_axis0(v, value_dtype)
    else:
        ids, n_groups = _flat_group_ids(obj)
        q, scale = _quantize_flat(v, ids, n_groups, value_dtype)
    return _replace_values(obj, q, scale)


# ---------------------------------------------------------------------------
# COO / CSR  (paper's CRS)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class COO:
    """Coordinate format - the universal interchange format."""

    rows: Array  # (nnz,) int32
    cols: Array  # (nnz,) int32
    vals: Array  # (nnz,) float
    shape: tuple[int, int]
    scale: Array = None  # (n_rows,) fp32 per-row scale for int8/fp8 values

    _static = ("shape",)

    @property
    def nnz(self) -> int:
        return int(np.asarray(self.vals).shape[0])

    def sorted_by_row(self) -> "COO":
        order = np.lexsort((_as_np(self.cols), _as_np(self.rows)))
        return COO(
            _as_np(self.rows)[order], _as_np(self.cols)[order], _as_np(self.vals)[order], self.shape
        )

    def to_dense(self) -> np.ndarray:
        d = np.zeros(self.shape, dtype=_as_np(self.vals).dtype)
        np.add.at(d, (_as_np(self.rows), _as_np(self.cols)), _as_np(self.vals))
        return d


@dataclass(frozen=True)
class CSR:
    """Compressed row storage -- the paper's CRS.

    Three arrays: row_ptr (offsets), col_idx, val.  Inner loop = sparse
    scalar product; algorithmic balance 10 B/F at fp64 (paper Sec. 2).
    """

    row_ptr: Array  # (n_rows+1,) int32
    col_idx: Array  # (nnz,) int32
    val: Array  # (nnz,) float
    shape: tuple[int, int]
    scale: Array = None  # (n_rows,) fp32 per-row scale for int8/fp8 values

    _static = ("shape",)

    @property
    def nnz(self) -> int:
        return int(np.asarray(self.val).shape[0])

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    def row_lengths(self) -> np.ndarray:
        rp = _as_np(self.row_ptr)
        return rp[1:] - rp[:-1]

    @staticmethod
    def from_coo(m: COO) -> "CSR":
        m = m.sorted_by_row()
        n_rows = m.shape[0]
        counts = np.bincount(_as_np(m.rows), minlength=n_rows)
        row_ptr = np.zeros(n_rows + 1, dtype=np.int32)
        np.cumsum(counts, out=row_ptr[1:])
        return CSR(row_ptr, _as_np(m.cols, np.int32), _as_np(m.vals), m.shape)

    def to_coo(self) -> COO:
        rows = np.repeat(np.arange(self.n_rows, dtype=np.int32), self.row_lengths())
        return COO(rows, _as_np(self.col_idx), _as_np(self.val), self.shape)

    def to_dense(self) -> np.ndarray:
        return self.to_coo().to_dense()

    @staticmethod
    def from_dense(d: np.ndarray, tol: float = 0.0) -> "CSR":
        d = np.asarray(d)
        rows, cols = np.nonzero(np.abs(d) > tol)
        return CSR.from_coo(COO(rows.astype(np.int32), cols.astype(np.int32), d[rows, cols], d.shape))


# ---------------------------------------------------------------------------
# ELL  (fully padded jagged format)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ELL:
    """ELLPACK: every row padded to max row length.

    2D dense operands (n_rows, max_nnz_row) -> perfectly regular VPU tiles.
    Padding entries have val=0 and col=0 (multiply-by-zero is harmless).
    Column-major ("jagged diagonal") iteration recovers the paper's JDS
    access pattern without the permutation.
    """

    col_idx: Array  # (n_rows, width) int32
    val: Array  # (n_rows, width) float
    shape: tuple[int, int]
    nnz: int
    scale: Array = None  # (n_rows,) fp32 per-row scale for int8/fp8 values

    _static = ("shape", "nnz")

    @property
    def width(self) -> int:
        return int(np.asarray(self.val).shape[1])

    @staticmethod
    def from_csr(m: CSR, width: int | None = None, pad_to: int = 1) -> "ELL":
        _require_materialized(m, "ELL.from_csr")
        _require_unquantized(m, "ELL.from_csr")
        lens = m.row_lengths()
        w = int(lens.max()) if lens.size else 0
        if width is not None:
            w = max(w, width)
        w = max(1, -(-w // pad_to) * pad_to)
        n = m.n_rows
        col = np.zeros((n, w), dtype=np.int32)
        val = np.zeros((n, w), dtype=_as_np(m.val).dtype)
        rp = _as_np(m.row_ptr)
        ci, v = _as_np(m.col_idx), _as_np(m.val)
        # vectorised scatter of the ragged rows into the padded 2D arrays
        rows = np.repeat(np.arange(n), lens)
        offs = np.arange(len(ci)) - np.repeat(rp[:-1], lens)
        col[rows, offs] = ci
        val[rows, offs] = v
        return ELL(col, val, m.shape, m.nnz)

    def to_dense(self) -> np.ndarray:
        d = np.zeros(self.shape, dtype=_as_np(self.val).dtype)
        n, w = _as_np(self.val).shape
        rows = np.repeat(np.arange(n), w)
        np.add.at(d, (rows, _as_np(self.col_idx).ravel()), _as_np(self.val).ravel())
        return d


# ---------------------------------------------------------------------------
# JDS  (the paper's jagged diagonals storage)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class JDS:
    """Jagged diagonals storage (paper Sec. 2).

    Rows are permuted by decreasing row length; the j-th entries of all rows
    form jagged diagonal j, stored consecutively.  ``perm`` maps permuted row
    index -> original row index (resvec_permuted[i] = resvec[perm[i]]).
    Inner loop = sparse vector triad; balance 18 B/F at fp64.
    """

    jd_ptr: Array  # (n_diags+1,) int32  offsets of each jagged diagonal
    col_idx: Array  # (nnz,) int32
    val: Array  # (nnz,) float
    perm: Array  # (n_rows,) int32 permuted->original row map
    shape: tuple[int, int]
    scale: Array = None  # (n_rows,) fp32 per-*permuted*-row scale (int8/fp8)

    _static = ("shape",)

    @property
    def n_diags(self) -> int:
        return int(np.asarray(self.jd_ptr).shape[0]) - 1

    @property
    def nnz(self) -> int:
        return int(np.asarray(self.val).shape[0])

    def diag_lengths(self) -> np.ndarray:
        jp = _as_np(self.jd_ptr)
        return jp[1:] - jp[:-1]

    @staticmethod
    def from_csr(m: CSR) -> "JDS":
        _require_materialized(m, "JDS.from_csr")
        _require_unquantized(m, "JDS.from_csr")
        lens = m.row_lengths()
        perm = np.argsort(-lens, kind="stable").astype(np.int32)
        sorted_lens = lens[perm]
        n_diags = int(sorted_lens.max()) if sorted_lens.size else 0
        rp = _as_np(m.row_ptr)
        ci, v = _as_np(m.col_idx), _as_np(m.val)
        cols_out, vals_out, jd_ptr = [], [], [0]
        for d in range(n_diags):
            # rows (in permuted order) long enough to contribute to diag d
            n_active = int(np.searchsorted(-sorted_lens, -d, side="left"))
            idx = rp[perm[:n_active]] + d
            cols_out.append(ci[idx])
            vals_out.append(v[idx])
            jd_ptr.append(jd_ptr[-1] + n_active)
        col_idx = np.concatenate(cols_out) if cols_out else np.zeros(0, np.int32)
        val = np.concatenate(vals_out) if vals_out else np.zeros(0, _as_np(m.val).dtype)
        return JDS(np.asarray(jd_ptr, np.int32), col_idx.astype(np.int32), val, perm, m.shape)

    def to_dense(self) -> np.ndarray:
        d = np.zeros(self.shape, dtype=_as_np(self.val).dtype)
        jp, ci, v, perm = map(_as_np, (self.jd_ptr, self.col_idx, self.val, self.perm))
        for k in range(self.n_diags):
            seg = slice(jp[k], jp[k + 1])
            rows = perm[: jp[k + 1] - jp[k]]
            d[rows, ci[seg]] += v[seg]
        return d


# ---------------------------------------------------------------------------
# SELL-C-sigma  (TPU-native blocked JDS; paper's NBJDS/RBJDS/SOJDS)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SELL:
    """SELL-C-sigma: rows sorted by length within windows of sigma rows, cut
    into chunks of C rows, each chunk padded to its own max row length and
    stored column-major (chunk-local jagged diagonals).

    - C is the TPU tile height (8 sublanes, or 128 for MXU-shaped tiles).
    - sigma is the paper's permutation scope: sigma = n_rows reproduces full
      JDS ordering; sigma = C reproduces near-original ordering (RBJDS-ish).
    - ``sort_cols`` additionally sorts entries of each in-chunk column
      segment by column index -- the paper's SOJDS stride optimisation.

    Storage: chunk c occupies val[chunk_ptr[c] : chunk_ptr[c+1]] reshaped to
    (width_c, C) column-major slabs -- i.e. RBJDS's "store all elements of a
    block consecutively".  For the Pallas kernel we also provide a fully
    padded 3D view (n_chunks, max_width, C) built by ``padded_views``.
    """

    chunk_ptr: Array  # (n_chunks+1,) int64 offsets into val (units of elements)
    chunk_width: Array  # (n_chunks,) int32 padded width of each chunk
    col_idx: Array  # (total,) int32, chunk-column-major, padded entries -> 0
    val: Array  # (total,) float, padded entries -> 0
    perm: Array  # (n_rows_padded,) int32 permuted->original row map (pad rows -> n_rows)
    shape: tuple[int, int]
    C: int
    sigma: int
    nnz: int
    scale: Array = None  # (n_chunks,) fp32 per-chunk scale for int8/fp8 values

    _static = ("shape", "C", "sigma", "nnz")

    @property
    def n_chunks(self) -> int:
        return int(np.asarray(self.chunk_width).shape[0])

    @staticmethod
    def from_csr(m: CSR, C: int = 8, sigma: int | None = None, sort_cols: bool = False,
                 pad_width_to: int = 1) -> "SELL":
        _require_materialized(m, "SELL.from_csr")
        _require_unquantized(m, "SELL.from_csr")
        n = m.n_rows
        # sigma=None -> the repo-wide default window (capped at n; pass
        # sigma=n_rows explicitly for the full-JDS sort)
        sigma = max(1, min(n, DEFAULT_SELL_SIGMA)) if sigma is None else max(1, sigma)
        lens = m.row_lengths()
        n_pad = -(-n // C) * C
        # sigma-window sort (stable) by decreasing length -- the shared
        # permutation used by the local and distributed packers alike
        perm = np.arange(n_pad, dtype=np.int32)
        perm[:n] = sigma_sort_order(lens, sigma)
        perm[n:] = n  # padding rows point one-past-end (handled by caller)
        plens = np.zeros(n_pad, dtype=np.int64)
        plens[:n] = lens[perm[:n]]
        n_chunks = n_pad // C
        cw = plens.reshape(n_chunks, C).max(axis=1)
        cw = np.maximum(1, -(-cw // pad_width_to) * pad_width_to).astype(np.int32)
        chunk_ptr = np.zeros(n_chunks + 1, dtype=np.int64)
        np.cumsum(cw.astype(np.int64) * C, out=chunk_ptr[1:])
        total = int(chunk_ptr[-1])
        col_idx = np.zeros(total, dtype=np.int32)
        val = np.zeros(total, dtype=_as_np(m.val).dtype)
        rp, ci, v = _as_np(m.row_ptr), _as_np(m.col_idx), _as_np(m.val)
        for c in range(n_chunks):
            w = int(cw[c])
            rows = perm[c * C : (c + 1) * C]
            ccol = np.zeros((w, C), dtype=np.int32)
            cval = np.zeros((w, C), dtype=val.dtype)
            for i, r in enumerate(rows):
                if r >= n:
                    continue
                L = int(lens[r])
                seg = slice(rp[r], rp[r] + L)
                if sort_cols:
                    order = np.argsort(ci[seg], kind="stable")
                    ccol[:L, i] = ci[seg][order]
                    cval[:L, i] = v[seg][order]
                else:
                    ccol[:L, i] = ci[seg]
                    cval[:L, i] = v[seg]
            col_idx[chunk_ptr[c] : chunk_ptr[c + 1]] = ccol.ravel()
            val[chunk_ptr[c] : chunk_ptr[c + 1]] = cval.ravel()
        return SELL(chunk_ptr, cw, col_idx, val, perm, m.shape, C, int(sigma), m.nnz)

    def padded_views(self, pad_width_to: int = 1) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Fully padded 3D views (n_chunks, W_max, C) for regular-grid kernels
        plus per-chunk widths. Memory cost: n_chunks * W_max * C elements."""
        cw = _as_np(self.chunk_width)
        wmax = max(1, -(-int(cw.max()) // pad_width_to) * pad_width_to)
        nc = self.n_chunks
        col = np.zeros((nc, wmax, self.C), dtype=np.int32)
        val = np.zeros((nc, wmax, self.C), dtype=_as_np(self.val).dtype)
        cp = _as_np(self.chunk_ptr)
        for c in range(nc):
            w = int(cw[c])
            col[c, :w] = _as_np(self.col_idx)[cp[c] : cp[c + 1]].reshape(w, self.C)
            val[c, :w] = _as_np(self.val)[cp[c] : cp[c + 1]].reshape(w, self.C)
        return col, val, cw

    def to_dense(self) -> np.ndarray:
        n, _ = self.shape
        d = np.zeros(self.shape, dtype=_as_np(self.val).dtype)
        cp, cw = _as_np(self.chunk_ptr), _as_np(self.chunk_width)
        ci, v, perm = _as_np(self.col_idx), _as_np(self.val), _as_np(self.perm)
        for c in range(self.n_chunks):
            w = int(cw[c])
            ccol = ci[cp[c] : cp[c + 1]].reshape(w, self.C)
            cval = v[cp[c] : cp[c + 1]].reshape(w, self.C)
            rows = perm[c * self.C : (c + 1) * self.C]
            for i, r in enumerate(rows):
                if r >= n:
                    continue
                mask = cval[:, i] != 0
                d[r, ccol[mask, i]] += cval[mask, i]
        return d


# ---------------------------------------------------------------------------
# BSR  (block CSR, MXU-native dense subblocks)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BSR:
    """Block CSR with dense (bm, bn) blocks.

    The paper notes dense subblocks can be exploited with specialised
    formats; on TPU a (bm, bn) >= (8,128) dense block executes on the
    MXU/VPU at full tile efficiency, and index traffic amortises over
    bm*bn elements: balance ~ (8 + 4/(bm*bn)) B/F -> the format of choice
    for structured sparse *weights*.
    """

    block_row_ptr: Array  # (n_brows+1,) int32
    block_col_idx: Array  # (n_blocks,) int32
    blocks: Array  # (n_blocks, bm, bn) float
    shape: tuple[int, int]
    block_shape: tuple[int, int]
    scale: Array = None  # (n_blocks,) fp32 per-block scale for int8/fp8 values

    _static = ("shape", "block_shape")

    @property
    def n_blocks(self) -> int:
        return int(np.asarray(self.block_col_idx).shape[0])

    @property
    def nnz(self) -> int:  # counting stored (dense-block) entries
        bm, bn = self.block_shape
        return self.n_blocks * bm * bn

    @staticmethod
    def from_dense(d: np.ndarray, block_shape: tuple[int, int] = (8, 128), tol: float = 0.0) -> "BSR":
        d = np.asarray(d)
        bm, bn = block_shape
        M, N = d.shape
        assert M % bm == 0 and N % bn == 0, f"dense {d.shape} not divisible by block {block_shape}"
        nbr, nbc = M // bm, N // bn
        tiles = d.reshape(nbr, bm, nbc, bn).transpose(0, 2, 1, 3)  # (nbr, nbc, bm, bn)
        keep = np.abs(tiles).max(axis=(2, 3)) > tol  # (nbr, nbc)
        rows, cols = np.nonzero(keep)
        blocks = tiles[rows, cols]
        brp = np.zeros(nbr + 1, dtype=np.int32)
        np.cumsum(np.bincount(rows, minlength=nbr), out=brp[1:])
        return BSR(brp, cols.astype(np.int32), blocks, d.shape, block_shape)

    def to_dense(self) -> np.ndarray:
        bm, bn = self.block_shape
        M, N = self.shape
        d = np.zeros((M, N), dtype=_as_np(self.blocks).dtype)
        brp = _as_np(self.block_row_ptr)
        bci = _as_np(self.block_col_idx)
        blocks = _as_np(self.blocks)
        for br in range(len(brp) - 1):
            for k in range(brp[br], brp[br + 1]):
                bc = bci[k]
                d[br * bm : (br + 1) * bm, bc * bn : (bc + 1) * bn] += blocks[k]
        return d

    def density(self) -> float:
        nbr = self.shape[0] // self.block_shape[0]
        nbc = self.shape[1] // self.block_shape[1]
        return self.n_blocks / max(1, nbr * nbc)


# ---------------------------------------------------------------------------
# DIA + remainder hybrid  (dense secondary diagonals split)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DIA:
    """Diagonal storage: ``data[k, i]`` is element (i, i + offsets[k]).

    Stride-1 access to the input vector (a shifted read), zero index traffic
    per element: balance ~ 6 B/F at fp64 against CRS's 10.  Only worthwhile
    for well-occupied diagonals -- exactly the Holstein-Hubbard structure
    (Fig. 5: ~60% of nnz in 12 secondary diagonals).
    """

    offsets: Array  # (n_diags,) int32
    data: Array  # (n_diags, n_rows) float; out-of-range entries are 0
    shape: tuple[int, int]
    scale: Array = None  # (n_diags,) fp32 per-diagonal scale for int8/fp8

    _static = ("shape",)

    @property
    def nnz(self) -> int:
        return int((np.asarray(self.data) != 0).sum())

    @staticmethod
    def from_csr(m: "CSR", max_diags: int | None = None) -> "DIA":
        """Pure diagonal storage of every populated (sub)diagonal.

        Only sensible when the matrix concentrates on few offsets (banded /
        stencil patterns); ``max_diags`` guards against accidentally
        materializing thousands of near-empty diagonals.
        """
        _require_materialized(m, "DIA.from_csr")
        _require_unquantized(m, "DIA.from_csr")
        coo = m.to_coo()
        rows = _as_np(coo.rows).astype(np.int64)
        cols = _as_np(coo.cols).astype(np.int64)
        vals = _as_np(coo.vals)
        offs = cols - rows
        uniq = np.unique(offs)
        if max_diags is not None and len(uniq) > max_diags:
            raise ValueError(
                f"matrix has {len(uniq)} populated diagonals > max_diags={max_diags}; "
                "use split_dia (hybrid) instead")
        data = np.zeros((len(uniq), m.shape[0]), dtype=vals.dtype)
        k = np.searchsorted(uniq, offs)
        np.add.at(data, (k, rows), vals)
        return DIA(uniq.astype(np.int32), data, m.shape)

    def to_dense(self) -> np.ndarray:
        n, m = self.shape
        d = np.zeros(self.shape, dtype=_as_np(self.data).dtype)
        for k, off in enumerate(_as_np(self.offsets)):
            i = np.arange(max(0, -off), min(n, m - off))
            d[i, i + off] += _as_np(self.data)[k, i]
        return d


@dataclass(frozen=True)
class HybridDIA:
    """The beyond-paper split format: DIA part + SELL remainder."""

    dia: DIA
    rest: SELL
    shape: tuple[int, int]

    _static = ("shape",)

    @property
    def nnz(self) -> int:
        return self.dia.nnz + self.rest.nnz

    def to_dense(self) -> np.ndarray:
        return self.dia.to_dense() + self.rest.to_dense()


def split_dia(m: CSR, min_occupancy: float = 0.5, max_diags: int = 16,
              C: int = 8, sigma: int | None = None) -> HybridDIA:
    """Split off well-occupied (sub)diagonals into DIA, remainder into SELL.

    ``min_occupancy`` is the fraction of the diagonal's full length that must
    be populated for it to be promoted to dense-diagonal storage.
    """
    _require_materialized(m, "split_dia")
    _require_unquantized(m, "split_dia")
    n, ncols = m.shape
    coo = m.to_coo()
    rows, cols, vals = map(_as_np, (coo.rows, coo.cols, coo.vals))
    offs = cols.astype(np.int64) - rows.astype(np.int64)
    uniq, counts = np.unique(offs, return_counts=True)
    diag_len = np.minimum(n, ncols) - np.abs(uniq)  # available length per offset
    occ = counts / np.maximum(1, diag_len)
    cand = np.argsort(-occ)
    chosen = [int(uniq[i]) for i in cand[:max_diags] if occ[i] >= min_occupancy]
    chosen_set = set(chosen)
    in_dia = np.isin(offs, list(chosen_set)) if chosen else np.zeros(len(offs), bool)
    # build DIA part
    offsets = np.asarray(sorted(chosen_set), dtype=np.int32)
    data = np.zeros((len(offsets), n), dtype=vals.dtype)
    if len(offsets):
        np.add.at(data, (np.searchsorted(offsets, offs[in_dia]), rows[in_dia]),
                  vals[in_dia])
    dia = DIA(offsets, data, m.shape)
    # remainder
    rsel = ~in_dia
    rest_csr = CSR.from_coo(COO(rows[rsel], cols[rsel], vals[rsel], m.shape))
    rest = SELL.from_csr(rest_csr, C=C, sigma=sigma)
    return HybridDIA(dia, rest, m.shape)


# ---------------------------------------------------------------------------
# matrix-free generated operators  (no index arrays at all)
# ---------------------------------------------------------------------------


def _divisors(n: int) -> list[int]:
    """Ascending divisors of ``n`` (n <= a few thousand in this repo)."""
    small = [d for d in range(1, int(n ** 0.5) + 1) if n % d == 0]
    return sorted({*small, *(n // d for d in small)})


def _periodic_rule(mask: np.ndarray) -> tuple[int, int, int] | None:
    """The minimal-period contiguous-run rule generating a populated-row mask.

    Returns ``(p, lo, hi)`` such that ``mask[i] == (lo <= i % p < hi)`` for
    all rows, with ``p`` the *minimal* period dividing ``len(mask)``, or
    ``None`` when no single contiguous run per period reproduces the mask
    (then the diagonal's pattern must be stored, not generated).
    """
    n = int(mask.shape[0])
    if not mask.any():
        return None
    for p in _divisors(n):
        pat = mask[:p]
        if not np.array_equal(np.tile(pat, n // p), mask):
            continue
        idx = np.flatnonzero(pat)
        lo, hi = int(idx[0]), int(idx[-1]) + 1
        # a non-contiguous minimal pattern stays non-contiguous in every
        # larger divisor (they are tiles of it) -- no point continuing
        return (p, lo, hi) if hi - lo == len(idx) else None
    return None


@dataclass(frozen=True)
class MatrixFreeOperator:
    """A structured operator stored as a pattern *descriptor*, not arrays.

    SpMV is bandwidth-bound (paper Sec. 2-3), and for stencil/banded/Holstein
    patterns the column index of every element is a pure function of its row:
    ``col = row + offset``, valid when ``lo <= row % period < hi`` (trivial
    rule ``(1, 0, 1)`` = the whole diagonal).  Kernels regenerate indices
    in-registers, so the index stream -- 4-8 B/nnz under CSR/ELL/SELL -- and,
    for constant diagonals, the value stream cost *zero* memory traffic.

    Per diagonal ``k`` (ascending ``offsets``):

    * ``gen_values[k]`` is a float -> fully generated: every rule-valid row
      holds that constant; nothing streamed.
    * ``gen_values[k]`` is None -> stored: the diagonal's values live in the
      next row of ``data`` (DIA-style dense ``(n_rows,)`` lane, zeros where
      unpopulated), with the trivial always-valid rule.

    ``data`` is the only pytree leaf (None when every diagonal is generated);
    the descriptor tuples are static aux data, so they hash into jit caches
    and the TuneDB signature.
    """

    data: Array  # (n_stored, n_rows) float, or None when all generated
    shape: tuple[int, int]
    offsets: tuple[int, ...]      # all populated diagonals, ascending
    periods: tuple[int, ...]      # per-diagonal validity period p
    los: tuple[int, ...]          # rule: lo <= row % p < hi
    his: tuple[int, ...]
    gen_values: tuple  # per-diagonal generated constant, or None = stored
    nnz: int
    stored_nnz: int               # nonzeros living in ``data``
    value_dtype: str              # canonical storage-precision name

    _static = ("shape", "offsets", "periods", "los", "his", "gen_values",
               "nnz", "stored_nnz", "value_dtype")

    @property
    def n_diags(self) -> int:
        return len(self.offsets)

    @property
    def n_stored(self) -> int:
        return sum(1 for g in self.gen_values if g is None)

    @property
    def n_generated(self) -> int:
        return self.n_diags - self.n_stored

    @property
    def gen_nnz(self) -> int:
        """Generated (zero-byte) elements: rule-valid rows per gen diagonal."""
        n = self.shape[0]
        return sum((n // p) * (hi - lo)
                   for p, lo, hi, g in zip(self.periods, self.los, self.his,
                                           self.gen_values) if g is not None)

    @staticmethod
    def from_csr(m: "CSR", max_diags: int = 256) -> "MatrixFreeOperator":
        """Detect the generated-diagonal structure of ``m`` exactly.

        A diagonal is *generated* when its values are all bitwise equal, its
        rows are duplicate-free and its populated-row mask is one contiguous
        run per minimal period dividing n_rows (stencil interiors, banded
        truncation at ``p = n`` included).  Everything else is stored as a
        dense DIA-style lane.  Raises ``ValueError`` on an empty matrix or
        one spread over more than ``max_diags`` diagonals -- matrix-free
        storage is for diagonal-structured operators only.
        """
        _require_unquantized(m, "MatrixFreeOperator.from_csr")
        n, _ncols = m.shape
        coo = m.to_coo()
        rows = _as_np(coo.rows).astype(np.int64)
        cols = _as_np(coo.cols).astype(np.int64)
        vals = _as_np(coo.vals)
        if rows.size == 0:
            raise ValueError("MatrixFreeOperator.from_csr: empty matrix")
        offs = cols - rows
        uniq = np.unique(offs)
        if len(uniq) > max_diags:
            raise ValueError(
                f"matrix has {len(uniq)} populated diagonals > "
                f"max_diags={max_diags}; matrix-free storage does not apply")
        offsets, periods, los, his, gen_values = [], [], [], [], []
        stored = []
        stored_nnz = 0
        for off in uniq.tolist():
            sel = offs == off
            r, v = rows[sel], vals[sel]
            rule = None
            if len(np.unique(r)) == len(r) and np.all(v == v[0]):
                mask = np.zeros(n, dtype=bool)
                mask[r] = True
                rule = _periodic_rule(mask)
            offsets.append(int(off))
            if rule is not None:
                p, lo, hi = rule
                periods.append(p)
                los.append(lo)
                his.append(hi)
                gen_values.append(float(v[0]))
            else:
                periods.append(1)
                los.append(0)
                his.append(1)
                gen_values.append(None)
                lane = np.zeros(n, dtype=vals.dtype)
                np.add.at(lane, r, v)
                stored.append(lane)
                stored_nnz += int((lane != 0).sum())
        data = np.stack(stored) if stored else None
        return MatrixFreeOperator(
            data=data, shape=m.shape, offsets=tuple(offsets),
            periods=tuple(periods), los=tuple(los), his=tuple(his),
            gen_values=tuple(gen_values), nnz=m.nnz, stored_nnz=stored_nnz,
            value_dtype=value_dtype_name(vals.dtype))

    def to_dense(self) -> np.ndarray:
        return materialize(self).to_dense()


def detect_matrix_free(m: CSR, max_diags: int = 256):
    """Cached ``MatrixFreeOperator.from_csr``; ``None`` when ``m`` has no
    affordable diagonal structure (or is quantized).  Never raises -- this is
    the probe ``perfmodel.select_format`` calls on every auto-format pick."""
    cache = getattr(m, "_mf_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(m, "_mf_cache", cache)
    if max_diags not in cache:
        try:
            cache[max_diags] = MatrixFreeOperator.from_csr(m, max_diags=max_diags)
        except (ValueError, TypeError):
            cache[max_diags] = None
    return cache[max_diags]


def materialize(op: MatrixFreeOperator) -> CSR:
    """Expand a ``MatrixFreeOperator`` back to explicit-index CSR.

    The one sanctioned escape hatch for structural converters: generated
    diagonals are expanded from their rules (boundary-clipped exactly as the
    kernels' zero-padded reads clip them), stored lanes drop their padding
    zeros.  Round-trips ``MatrixFreeOperator.from_csr`` bit-exactly on
    matrices without explicit stored zeros.
    """
    if not isinstance(op, MatrixFreeOperator):
        raise TypeError(f"materialize expects a MatrixFreeOperator, "
                        f"got {type(op).__name__}")
    n, ncols = op.shape
    dtype = VALUE_DTYPES.get(op.value_dtype, np.float32)
    data = None if op.data is None else _as_np(op.data)
    rows_l, cols_l, vals_l = [], [], []
    k_stored = 0
    for k, off in enumerate(op.offsets):
        gv = op.gen_values[k]
        if gv is None:
            lane = data[k_stored]
            k_stored += 1
            r = np.flatnonzero(lane).astype(np.int64)
            v = lane[r]
        else:
            p, lo, hi = op.periods[k], op.los[k], op.his[k]
            i = np.arange(n, dtype=np.int64)
            r = i[(i % p >= lo) & (i % p < hi)]
            v = np.full(len(r), gv, dtype=dtype)
        keep = (r + off >= 0) & (r + off < ncols)
        r = r[keep]
        rows_l.append(r.astype(np.int32))
        cols_l.append((r + off).astype(np.int32))
        vals_l.append(np.asarray(v[keep], dtype=dtype))
    return CSR.from_coo(COO(np.concatenate(rows_l), np.concatenate(cols_l),
                            np.concatenate(vals_l), op.shape))


# ---------------------------------------------------------------------------
# registry / stats
# ---------------------------------------------------------------------------

FORMATS = {"csr": CSR, "ell": ELL, "jds": JDS, "sell": SELL, "bsr": BSR, "dia": DIA, "hybrid": HybridDIA,
           "matrix_free": MatrixFreeOperator}


def convert(m: CSR, fmt: str, value_dtype: str | None = None, **kw):
    """Convert ``m`` to ``fmt``, optionally storing values as ``value_dtype``.

    A quantized source is dequantized first and re-quantized in the target
    format's own scale-group layout (per-row scales cannot be reinterpreted
    as per-diagonal ones); without an explicit ``value_dtype`` the source's
    storage dtype is preserved.
    """
    if isinstance(m, MatrixFreeOperator) and fmt != "matrix_free":
        raise TypeError(
            f"convert: cannot repack a MatrixFreeOperator into {fmt!r} -- it "
            "carries a pattern descriptor, not index arrays; materialize(op) "
            "is the escape hatch back to explicit CSR")
    if getattr(m, "scale", None) is not None:
        if value_dtype is None:
            value_dtype = container_value_dtype(m)
        m = dequantize(m)
    out = _convert(m, fmt, **kw)
    if value_dtype is not None:
        out = with_value_dtype(out, value_dtype)
    return out


def _convert(m: CSR, fmt: str, **kw):
    if fmt == "csr":
        return m
    if fmt == "ell":
        return ELL.from_csr(m, **kw)
    if fmt == "jds":
        return JDS.from_csr(m)
    if fmt == "sell":
        return SELL.from_csr(m, **kw)
    if fmt == "bsr":
        return BSR.from_dense(m.to_dense(), **kw)
    if fmt == "dia":
        return DIA.from_csr(m, **kw)
    if fmt == "hybrid":
        return split_dia(m, **kw)
    if fmt == "matrix_free":
        if isinstance(m, MatrixFreeOperator):
            return m
        return MatrixFreeOperator.from_csr(m, **kw)
    raise ValueError(f"unknown format {fmt!r}")


def matrix_stats(m: CSR) -> dict:
    """Compressed sparsity-pattern statistics, paper Fig. 5-style: the inputs
    the performance model needs instead of the full pattern."""
    lens = m.row_lengths()
    ci = _as_np(m.col_idx)
    rp = _as_np(m.row_ptr)
    strides = np.diff(ci)
    # remove the row-crossing strides (paper: backward jumps at row starts)
    row_starts = rp[1:-1]
    inner_mask = np.ones(len(strides), bool)
    valid = (row_starts > 0) & (row_starts < m.nnz)
    inner_mask[row_starts[valid] - 1] = False
    inner = strides[inner_mask]
    cross = strides[~inner_mask]
    coo = m.to_coo()
    offs = _as_np(coo.cols).astype(np.int64) - _as_np(coo.rows).astype(np.int64)
    uq, cnt = np.unique(offs, return_counts=True)
    order = np.argsort(-cnt)
    return {
        "n_rows": m.shape[0],
        "n_cols": m.shape[1],
        "nnz": m.nnz,
        "nnz_per_row_mean": float(lens.mean()) if lens.size else 0.0,
        "nnz_per_row_std": float(lens.std()) if lens.size else 0.0,
        "nnz_per_row_max": int(lens.max()) if lens.size else 0,
        "mean_inner_stride": float(np.abs(inner).mean()) if inner.size else 0.0,
        "frac_backward_jumps": float((np.concatenate([inner, cross]) < 0).mean()) if m.nnz > 1 else 0.0,
        "frac_stride_le_8": float((np.abs(inner) <= 8).mean()) if inner.size else 0.0,
        "top_diag_offsets": uq[order[:16]].tolist(),
        "top_diag_counts": cnt[order[:16]].tolist(),
        "frac_nnz_top12_diags": float(cnt[order[:12]].sum() / max(1, m.nnz)),
        "bandwidth": int(np.abs(offs).max()) if m.nnz else 0,
    }


for _cls in (COO, CSR, ELL, JDS, SELL, BSR, DIA, HybridDIA, MatrixFreeOperator):
    _pytree_dataclass(_cls)
