"""Compiled SpMV execution plans: preprocess once, execute many times.

The paper's workloads never do *one* SpMV: the Lanczos eigensolver applies
the same Hamiltonian for every iteration, and the serving engine streams the
same weights for every decoded token.  ``SpMVPlan.compile`` turns a one-shot
format container into a reusable executor:

1. **Cached preprocessing** — all host-derived metadata (CSR row-ids, SELL
   padded ``(nc, W, C)`` views, JDS segment tables, DIA shifted-slice geometry)
   is computed exactly once per matrix and pinned on the container
   (``core.spmv`` build-once caches), then device-put once.
2. **Vectorized kernels** — every format executes as O(1) traced ops
   (gather + segment-sum / einsum), never an O(n_chunks) host-unrolled
   scatter chain.
3. **Registry-backed kernel selection** — every executor comes from
   ``repro.kernels.registry``, the one table of ``(format, op, backend)``
   entries.  ``backend="auto"`` runs the registered capability probes
   (platform, dtype, VMEM-fit tiling) and ranks the survivors with the
   execution-aware roofline (``perfmodel.predict_exec`` through each
   entry's cost hook), memoizing the choice on the container; an explicit
   backend name compiles that entry (falling back to the XLA formulation
   when the format has no such entry or its probe rejects the operand —
   e.g. ``backend="pallas"`` for a SELL whose tiling cannot fit VMEM).
   Pallas tiling choices come from the entries' autotune hooks
   (``kernels.sell.sell_autotune`` via ``perfmodel.select_pallas_blocks``).
4. **Cached jitted executors** — ``plan(x)`` (SpMV) and ``plan.spmm(X)``
   (multi-vector) are jitted once; plans themselves are memoized on the
   container, so ``compile`` is idempotent and free after the first call.
   The matrix is put on the device once and passed to the jitted
   executors as *arguments*: a program never embeds it as constants, so
   its size (and its compile time) does not grow with nnz.

``chip`` parameterizes the roofline (prediction + VMEM budget); ``backend``
is ``"auto" | "xla" | "pallas" | "pallas_interpret" | "loop_reference"``
(``"ref"`` aliases ``"xla"``; ``"pallas"`` off-TPU resolves to the
interpreter entry, exactly as before).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..kernels import registry as R
from ..utils import spans
from ..utils.hw import ChipSpec, TPU_V5E
from . import perfmodel as PM
from .formats import (
    BSR, COO, CSR, DIA, ELL, JDS, SELL, HybridDIA, MatrixFreeOperator)
from .planconfig import PlanConfig, coerce_config  # noqa: F401  (re-export)

_FMT_NAMES = {
    COO: "coo", CSR: "csr", ELL: "ell", JDS: "jds", SELL: "sell",
    BSR: "bsr", DIA: "dia", HybridDIA: "hybrid",
    MatrixFreeOperator: "matrix_free",
}


@dataclass(frozen=True)
class PlanReport:
    """What the plan decided and what the model predicts for it."""

    format: str
    shape: tuple
    nnz: int
    kernel: str                     # "xla" | "pallas" | "pallas-interpret"
    chunk_block: int | None         # SELL Pallas tiling (None for XLA paths)
    width_block: int | None
    vmem_bytes: int | None          # working-set claim of the Pallas tiling
    balance_bytes_per_flop: float
    predicted_gflops: float
    predicted_time_s: float
    bound: str                      # "memory" | "compute"


class SpMVPlan:
    """A compiled SpMV executor: ``plan(x) -> y`` and ``plan.spmm(X) -> Y``.

    ``kernel(operands, x)`` / ``kernel_multi(operands_multi, X)`` are the
    jitted executors and ``operands`` / ``operands_multi`` the device
    arrays they read (exposed so benchmarks and compile checks can
    ``.lower()`` them); ``apply`` / ``apply_multi`` call them unchecked.
    The executors are named ``spmv_<format>_<kernel>`` and
    ``spmm_<format>_<kernel>`` (``jit_spmv_hybrid_xla`` in a profile).
    """

    def __init__(self, matrix, report: PlanReport, ck_v: R.CompiledKernel,
                 ck_m: R.CompiledKernel):
        self.matrix = matrix
        self.report = report
        self.kernel = jax.jit(_named(ck_v.kernel, "spmv", report.format, ck_v.label))
        self.kernel_multi = jax.jit(_named(ck_m.kernel, "spmm", report.format, ck_m.label))
        self.operands = ck_v.operands
        self.operands_multi = ck_m.operands

    def apply(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.kernel(self.operands, x)

    def apply_multi(self, X: jnp.ndarray) -> jnp.ndarray:
        return self.kernel_multi(self.operands_multi, X)

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.spmv(x)

    def spmv(self, x: jnp.ndarray) -> jnp.ndarray:
        """One SpMV through the cached jitted executor.

        Args:
            x: input vector of shape (N,) for an (M, N) operator.

        Returns:
            y = A @ x of shape (M,).  Raises ValueError on a shape
            mismatch (the XLA gather would clamp indices silently).
        """
        from ..testing import faults
        if x.shape != (self.report.shape[1],):  # XLA gather would clamp, silently
            raise ValueError(f"x has shape {x.shape}, expected ({self.report.shape[1]},)")
        spec = faults.fire("plan.spmv", ctx={"op": "spmv", "format": self.report.format,
                                             "kernel": self.report.kernel})
        y = self.apply(x)
        return faults.poison(y, spec) if spec is not None else y

    def spmm(self, X: jnp.ndarray) -> jnp.ndarray:
        """Multi-vector SpMV: X (N, K) -> Y (M, K), one fused pass.

        The matrix is streamed once for all K columns — the serving
        layer's batching lever (see ``perfmodel.spmm_balance_of``).
        """
        from ..testing import faults
        if X.ndim != 2 or X.shape[0] != self.report.shape[1]:
            raise ValueError(f"X has shape {X.shape}, expected ({self.report.shape[1]}, K)")
        spec = faults.fire("plan.spmm", ctx={"op": "spmm", "format": self.report.format,
                                             "kernel": self.report.kernel})
        Y = self.apply_multi(X)
        return faults.poison(Y, spec) if spec is not None else Y

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        r = self.report
        return (f"SpMVPlan({r.format}, {r.shape}, nnz={r.nnz}, kernel={r.kernel}, "
                f"pred={r.predicted_gflops:.2f} GF/s)")

    # -- compilation --------------------------------------------------------

    @staticmethod
    def compile(matrix, config: PlanConfig | None = None,
                **kwargs) -> "SpMVPlan":
        """Build (or fetch the memoized) plan for ``matrix``.

        ``config`` is a :class:`core.planconfig.PlanConfig` — the one
        record of every compile option (format, value_dtype, chip, am,
        backend, chunk_block, width_block, validate, tuning, and the
        SELL-C-sigma ``sigma`` / ``permute`` pair); see its docstring and
        the historical per-option semantics below.  Bare kwargs remain
        accepted as deprecated aliases: they emit one
        ``DeprecationWarning`` and are folded into an equivalent config
        (passing both is an error).

        Option semantics (unchanged from the kwarg era):

        * ``format`` — ``None`` plans the container as-is; a concrete name
          ("sell", "dia", ...) converts a CSR/COO container first (cached
          on the source); ``"auto"`` lets ``perfmodel.select_format``
          pick — now including an autotuned SELL sigma window.
        * ``value_dtype`` — value-storage precision; narrow dtypes cut
          streamed bytes, int8/fp8 quantize with per-group fp32 scales,
          kernels accumulate in >= f32.
        * ``chip`` / ``am`` — roofline parameters / access-model byte
          widths (``am=None`` derives from the stored dtype).
        * ``backend`` — "auto" | "xla" | "pallas" ("ref" aliases "xla");
          "pallas" off-TPU resolves to the interpreter.
        * ``chunk_block`` / ``width_block`` — Pallas tiling overrides.
        * ``validate`` — "strict" | "repair" | "off"; ``None`` inherits
          ("off" here, the server's policy under ``register``).
        * ``tuning`` — a ``core.tunedb.TuneDB`` or path; measured winners
          override the auto rankings (warm path).
        * ``sigma`` / ``permute`` — the SELL sorting window: ``sigma=None``
          keeps the default window (and autotunes under ``format="auto"``),
          ``permute=False`` forces identity row order.  ``plan(x)`` always
          returns rows in original order regardless (the kernels apply the
          inverse scatter).

        Returns:
            The compiled (memoized) ``SpMVPlan``; ``plan.report`` records
            what was decided and what the roofline predicts for it.
        """
        cfg = coerce_config(config, kwargs, api="SpMVPlan.compile")
        chip, am, backend = cfg.chip, cfg.am, cfg.backend
        validate = cfg.validate if cfg.validate is not None else "off"
        tuning = cfg.tuning
        if validate != "off":
            from .validate import validate_matrix
            matrix = validate_matrix(matrix, policy=validate)
        if tuning is not None:
            from .tunedb import open_db
            tuning = open_db(tuning)
        if cfg.format is not None:
            matrix = resolve_format(matrix, cfg.format, chip=chip, am=am,
                                    backend=backend, tuning=tuning,
                                    sigma=1 if not cfg.permute else cfg.sigma,
                                    convert_kwargs=cfg.sell_kwargs())
        if cfg.value_dtype is not None:
            from . import formats as F
            matrix = _convert_cached(matrix, _FMT_NAMES.get(type(matrix)),
                                     {}, value_dtype=cfg.value_dtype) \
                if type(matrix) in (F.CSR, F.COO) \
                else F.with_value_dtype(matrix, cfg.value_dtype)
        fmt = _FMT_NAMES.get(type(matrix))
        if fmt is None:
            raise TypeError(f"no plan for {type(matrix).__name__}")
        _resolve_backend(backend)  # validate for every format, not just SELL
        if am is None:
            am = PM.access_model_for(matrix, chip)
        key = (fmt, backend, cfg.chunk_block, cfg.width_block, chip.name,
               am.value_bytes, am.index_bytes,
               getattr(tuning, "token", None))
        cache = _memo(matrix, "_spmv_plans")
        plan = cache.get(key)
        if plan is None:
            plan = _compile(matrix, fmt, chip, am, backend, cfg.chunk_block,
                            cfg.width_block, tuning)
            cache[key] = plan
        return plan


# ---------------------------------------------------------------------------
# format resolution (the "auto" end of the corpus-validated selector)
# ---------------------------------------------------------------------------


def resolve_format(matrix, format: str, *, chip: ChipSpec = TPU_V5E,
                   am: PM.AccessModel | None = None, backend: str = "auto",
                   tuning=None, convert_kwargs: dict | None = None,
                   **select_kw):
    """Return ``matrix`` converted to ``format`` (``"auto"`` = model's pick).

    A CSR/COO container is converted (and the converted container cached on
    it, so every consumer — eigensolver, server, benchmarks — shares one
    conversion per format); a container already in a concrete format passes
    through when it matches, and is rejected otherwise (silently re-packing
    a hand-chosen format would hide a bug).  For ``"auto"`` on an already
    concrete container the upstream choice stands.  ``tuning`` (a
    ``core.tunedb.TuneDB``) lets the measured warm path decide the
    ``"auto"`` pick; ``None`` keeps the model-only cold path.
    ``convert_kwargs`` (e.g. an explicit SELL ``sigma``) applies to
    explicit conversions of sigma-aware formats; the ``"auto"`` path takes
    its kwargs — including the autotuned sigma — from the selector's
    choice instead.
    """
    fmt = _FMT_NAMES.get(type(matrix))
    if fmt is None:
        raise TypeError(f"no plan for {type(matrix).__name__}")
    if format == "auto":
        if fmt not in ("csr", "coo"):
            return matrix
        be = _resolve_backend(backend)
        key = (chip, am, be, getattr(tuning, "token", None),
               tuple(sorted(select_kw.items())))
        memo = _memo(matrix, "_format_choices")
        choice = memo.get(key)
        if choice is None:
            src = _as_csr_container(matrix)
            with _plan_span("plan.select"):
                choice = PM.select_format(src, am=am, chip=chip, backend=be,
                                          tuning=tuning, **select_kw)
            memo[key] = choice
        return _convert_cached(matrix, choice.format, choice.convert_kwargs)
    if format == fmt:
        return matrix
    if fmt not in ("csr", "coo"):
        raise ValueError(f"cannot convert a {fmt} container to {format!r}; "
                         "pass the CSR/COO source instead")
    kw = dict(convert_kwargs or {}) if format in ("sell", "hybrid") else {}
    return _convert_cached(matrix, format, kw)


def _as_csr_container(matrix):
    from .formats import CSR
    if isinstance(matrix, CSR):
        return matrix
    return _convert_cached(matrix, "csr", {})


def _memo(matrix, attr: str) -> dict:
    """A dict pinned on the (frozen) container under ``attr``."""
    cache = getattr(matrix, attr, None)
    if cache is None:
        cache = {}
        object.__setattr__(matrix, attr, cache)
    return cache


def _convert_cached(matrix, fmt: str, kw: dict, value_dtype: str | None = None):
    from .formats import COO, CSR, convert, with_value_dtype
    cache = _memo(matrix, "_fmt_cache")
    key = (fmt, value_dtype, tuple(sorted(kw.items())))
    obj = cache.get(key)
    if obj is None:
        with _plan_span("plan.convert"):
            src = CSR.from_coo(matrix) if isinstance(matrix, COO) else matrix
            obj = src if fmt == "csr" else convert(src, fmt, **kw)
            if value_dtype is not None:
                obj = with_value_dtype(obj, value_dtype)
        if obj is not src:
            # back-reference for the tuning DB: a converted container is
            # signed through its source CSR's pattern (tunedb.signature_of)
            try:
                object.__setattr__(obj, "_tune_src", src)
            except AttributeError:
                pass
        cache[key] = obj
    return obj


# ---------------------------------------------------------------------------
# compilation internals
# ---------------------------------------------------------------------------


_PLAN_SPAN = threading.local()


@contextmanager
def _plan_span(name: str):
    """``spans.span(name)`` unless another plan span is open on this thread:
    the outermost owns the time, so ``plan.select``, ``plan.convert`` and
    ``plan.build`` never nest (a tuning DB's freshness check converts its
    candidates inside ``plan.select``)."""
    if getattr(_PLAN_SPAN, "open", False):
        yield
        return
    _PLAN_SPAN.open = True
    try:
        with spans.span(name):
            yield
    finally:
        _PLAN_SPAN.open = False


def _named(kernel, op: str, fmt: str, label: str):
    """``kernel`` under the function name ``<op>_<fmt>_<label>``, which
    ``jax.jit`` gives the executor's program (``-`` becomes ``_``)."""
    def executor(operands, x):
        return kernel(operands, x)
    executor.__name__ = executor.__qualname__ = f"{op}_{fmt}_{label}".replace("-", "_")
    return executor


def _resolve_backend(backend: str) -> str:
    """Normalize a plan-level backend name to a registry backend.

    ``"pallas"`` keeps its historical meaning — the Pallas kernels, compiled
    on TPU and through the interpreter elsewhere — by resolving to the
    ``pallas_interpret`` registry entries off-TPU.
    """
    if backend == "auto":
        return "auto"
    if backend in ("ref", "xla"):
        return "xla"
    if backend == "pallas":
        return "pallas" if jax.default_backend() == "tpu" else "pallas_interpret"
    if backend in ("pallas_interpret", "loop_reference"):
        return backend
    raise ValueError(f"unknown backend {backend!r}; expected 'auto', 'xla', "
                     "'ref', 'pallas', 'pallas_interpret' or 'loop_reference'")


#: report/kernel label -> the perfmodel stream-byte regime it executes
_LABEL_STREAM = {"xla": "xla", "pallas": "pallas",
                 "pallas-interpret": "pallas_interpret",
                 "loop": "loop_reference"}


def _report(matrix, fmt: str, chip: ChipSpec, am: PM.AccessModel, kernel: str,
            choice: PM.BlockChoice | None = None) -> PlanReport:
    balance = PM.balance_of(matrix, am,
                            backend=_LABEL_STREAM.get(kernel, "xla"))
    pred = PM.predict(fmt, balance, matrix.nnz, chip=chip)
    return PlanReport(
        format=fmt, shape=tuple(matrix.shape), nnz=matrix.nnz, kernel=kernel,
        chunk_block=choice.chunk_block if choice else None,
        width_block=choice.width_block if choice else None,
        vmem_bytes=choice.vmem_bytes if choice else None,
        balance_bytes_per_flop=balance,
        predicted_gflops=pred.gflops,
        predicted_time_s=pred.time_s,
        bound=pred.bound,
    )


def _pick_entry(matrix, fmt: str, op: str, backend: str,
                ctx: R.KernelContext) -> str:
    """Resolve one (format, op) to a concrete registry backend.

    ``"auto"`` probes + ranks through the registry; an explicit backend is
    honored when its entry exists and its probe accepts the operand, and
    degrades to the XLA formulation otherwise (the historical behavior:
    ``backend="pallas"`` on a format without a Pallas kernel, or a SELL
    whose tiling cannot fit VMEM, compiles the XLA path).
    """
    if backend == "auto":
        with _plan_span("plan.select"):
            be, _ = R.select_backend(matrix, fmt, op, ctx)
        return be
    if R.has(fmt, op, backend) and R.get(fmt, op, backend).probe(matrix, ctx).ok:
        return backend
    return "xla"


def _compile(matrix, fmt, chip, am, backend, chunk_block, width_block,
             tuning=None) -> SpMVPlan:
    ctx = R.KernelContext(chip=chip, am=am, chunk_block=chunk_block,
                          width_block=width_block, tuning=tuning)
    be = _resolve_backend(backend)
    # "pallas" off-TPU has always meant: SpMV through the interpreter (the
    # test-coverage path), SpMM on the fused XLA formulation — the
    # interpreter's multi-vector pass is orders slower and was never the
    # historical behavior.  Asking for "pallas_interpret" BY NAME opts into
    # the interpreter for both ops (what the parity suite exercises).
    be_mm = "xla" if (backend == "pallas" and be == "pallas_interpret") else be
    be_v = _pick_entry(matrix, fmt, "spmv", be, ctx)
    be_m = _pick_entry(matrix, fmt, "spmm", be_mm, ctx)
    with _plan_span("plan.build"):
        ck_v = R.build(matrix, fmt, "spmv", be_v, ctx)
        ck_m = R.build(matrix, fmt, "spmm", be_m, ctx)
    choice = ck_v.choice if isinstance(ck_v.choice, PM.BlockChoice) else None
    return SpMVPlan(matrix, _report(matrix, fmt, chip, am, ck_v.label, choice),
                    ck_v, ck_m)


# ---------------------------------------------------------------------------
# convenience
# ---------------------------------------------------------------------------


def compile_plan(matrix, config: PlanConfig | None = None, **kw) -> SpMVPlan:
    """Alias of ``SpMVPlan.compile`` for functional call sites."""
    return SpMVPlan.compile(matrix, config, **kw)


def plan_all_formats(m: CSR, *, formats=("csr", "ell", "jds", "sell", "hybrid"),
                     chip: ChipSpec = TPU_V5E, backend: str = "auto", **conv_kw):
    """Convert + plan a CSR matrix into each requested format.

    Returns {name: SpMVPlan}; the paper's "hint to the respective optimal
    storage scheme" is then just ``min`` over ``plan.report.predicted_time_s``.
    """
    from .formats import convert

    cfg = PlanConfig(chip=chip, backend=backend)
    plans = {}
    for fmt in formats:
        obj = convert(m, fmt, **conv_kw.get(fmt, {}))
        plans[fmt] = SpMVPlan.compile(obj, cfg)
    return plans
