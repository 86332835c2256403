"""The unified kernel registry: one backend-dispatch layer for every
``(format, op, backend)`` combination in the framework.

The paper's central lesson is that the *same* sparse storage scheme needs
different computational kernels on different architectures (cache-based CRS
loops vs vector-friendly JDS), and Kreutzer et al. (arXiv:1307.6209) extend
this to SELL-C-sigma, whose kernel still must be specialized per SIMD width.
This module is that lesson as infrastructure: every kernel in the repo —
the vectorized XLA formulations, the Pallas TPU kernels, the paper-fidelity
loop traversals, and the distributed slab multiplies — registers here under
a declarative key, and every consumer (``core.plan``, ``core.
distributed_plan``, ``serve.engine``, benchmarks) dispatches through one
table instead of carrying its own ad-hoc selection logic.

Key space
---------
* ``format``  — a ``core.formats`` container name (``csr``, ``sell``, ...)
  or a distributed slab pack (``slab_ell`` / ``slab_sell``).
* ``op``      — ``spmv`` (vector) or ``spmm`` (multi-vector).
* ``backend`` — one of :data:`BACKENDS`:

  - ``xla``              — the fused gather/segment-sum/einsum formulations
                           (the fast path on CPU and the universal fallback);
  - ``pallas``           — compiled Pallas TPU kernels (TPU only);
  - ``pallas_interpret`` — the same kernels through the Pallas interpreter
                           (off-TPU only; the CI validation mode);
  - ``loop_reference``   — the paper-faithful per-diagonal / per-chunk loop
                           traversals: slow, obviously correct, the parity
                           oracle every other entry is tested against
                           (off-TPU only, like the interpreter).

Each :class:`KernelEntry` carries three hooks:

* ``probe(matrix, ctx) -> Capability`` — can this entry run *here* for
  *this* operand (platform, dtype, shape/tiling constraints)?  Probes
  must never raise for unsupported combinations: they return
  ``Capability(False, reason)`` so callers can skip, not crash.
* ``cost(matrix, ctx) -> float`` — predicted seconds for one call, through
  ``core.perfmodel.predict_exec`` with the entry's backend-specific stream
  bytes (flat vs padded SELL views, see ``perfmodel.balance_of``).
* ``autotune(matrix, ctx) -> choice`` — optional tiling selection (e.g.
  the SELL Pallas ``(chunk_block, width_block)`` pick), shared by the plan
  layer and the distributed planner instead of being duplicated in each.

``backend="auto"`` selection = run every probe, rank the surviving entries
by ``cost``, memoize the winner on the container.  ``python -m
repro.kernels.registry --list`` prints the registered table (the CI
``kernel-matrix`` step publishes it to the step summary).
"""
from __future__ import annotations

import argparse
import functools
import sys
from dataclasses import dataclass
from typing import Callable

import jax

from ..utils.hw import TPU_V5E, ChipSpec

OPS = ("spmv", "spmm")
BACKENDS = ("xla", "pallas", "pallas_interpret", "loop_reference")

#: canonical value-dtype names (mirrors ``core.formats.VALUE_DTYPES`` —
#: restated here so the registry stays import-light at module load)
ALL_VALUE_DTYPES = ("f64", "f32", "bf16", "f16", "fp8_e4m3", "int8")
#: the TPU vector unit has no f64; everything narrower upcasts to f32
PALLAS_VALUE_DTYPES = ("f32", "bf16", "f16", "fp8_e4m3", "int8")
#: the BELL MXU kernel streams blocks with no per-block scale plumbing, so
#: its Pallas entries take native float storage only
FLOAT_PALLAS_VALUE_DTYPES = ("f32", "bf16", "f16")

#: ranking derates for backends whose execution mode the perfmodel's
#: efficiency tables don't cover: the Pallas interpreter evaluates the grid
#: step-by-step through jax ops (orders slower than either real backend),
#: and the loop references trace O(n_chunks) host-unrolled segments.  They
#: stay *rankable* (an explicit request still compiles) but can never win
#: an auto selection against a real backend.
_BACKEND_DERATE = {"xla": 1.0, "pallas": 1.0,
                   "pallas_interpret": 1e-4, "loop_reference": 1e-3}


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@dataclass(frozen=True)
class KernelContext:
    """Everything a build/probe/cost hook may need beyond the operand.

    ``am`` is a ``perfmodel.AccessModel`` (left untyped to keep this module
    import-light); ``chunk_block``/``width_block``/``tile`` are optional
    user overrides of the autotune hooks' choices.  ``tuning`` is an
    optional ``core.tunedb.TuneDB``: when set, ``select_backend`` consults
    its measured winners before falling back to the cost-hook ranking.
    """

    chip: ChipSpec = TPU_V5E
    am: object = None                 # None -> perfmodel.TPU_FP32 at use site
    chunk_block: int | None = None
    width_block: int | None = None
    tile: int | None = None
    tuning: object = None             # None -> cold (model-only) selection

    def access_model(self):
        if self.am is not None:
            return self.am
        from ..core import perfmodel as PM
        return PM.TPU_FP32


@dataclass(frozen=True)
class Capability:
    """Outcome of a probe: can this entry run for this operand, here?"""

    ok: bool
    reason: str = ""

    def __bool__(self) -> bool:  # allows ``if probe(...):``
        return self.ok


CAP_OK = Capability(True)


@dataclass
class CompiledKernel:
    """What a build hook returns: the executor plus its provenance.

    ``kernel(operands, *args)`` is pure in its arguments: the matrix arrays
    it reads arrive as ``operands`` (a pytree placed on the device once, at
    build time), so a caller that jits ``kernel`` passes the matrix as jit
    *arguments* and the compiled program never embeds it as constants.
    ``kernel`` is *not* jitted — callers (the plan layer) jit it exactly
    once, or run it eagerly through :meth:`fn` (the parity suite, loop
    oracles).
    """

    kernel: Callable
    label: str                      # plan-report kernel label ("xla", ...)
    choice: object | None = None    # e.g. perfmodel.BlockChoice (Pallas SELL)
    operands: object = ()           # pytree of device arrays ``kernel`` reads

    def fn(self, *args):
        """Eager call: ``kernel`` applied to this build's own operands."""
        return self.kernel(self.operands, *args)

    def jitted(self) -> Callable:
        """``f(*args)``: ``kernel`` jitted, with the operands passed to it
        as arguments on every call."""
        return functools.partial(jax.jit(self.kernel), self.operands)


def closure_kernel(fn: Callable, label: str, choice=None) -> CompiledKernel:
    """Wrap an executor that reads its container directly (the loop
    oracles, the slab multiplies whose arrays arrive per call): it takes
    no operands, so under jit whatever it closes over is a constant."""
    return CompiledKernel(lambda _ops, *args: fn(*args), label, choice)


@dataclass(frozen=True)
class KernelEntry:
    """One registered ``(format, op, backend)`` implementation."""

    format: str
    op: str
    backend: str
    build: Callable                       # build(matrix, ctx) -> CompiledKernel
    probe: Callable                       # probe(matrix, ctx) -> Capability
    cost: Callable                        # cost(matrix, ctx) -> seconds
    autotune: Callable | None = None      # autotune(matrix, ctx) -> choice
    auto: bool = True                     # eligible for backend="auto"
    description: str = ""
    #: value-storage dtypes this entry accepts; the registered probe is
    #: wrapped with a gate that rejects containers stored outside this set
    value_dtypes: tuple = ALL_VALUE_DTYPES

    @property
    def key(self) -> tuple:
        return (self.format, self.op, self.backend)


class BackendUnavailable(LookupError):
    """No registered entry can run this (format, op) here."""


_TABLE: dict[tuple, KernelEntry] = {}
_POPULATED = False


def _ensure_populated() -> None:
    """Import the kernel modules so their entries land in the table.

    Deferred (not at module import) so ``registry`` itself stays
    import-light and cycle-free; idempotent.
    """
    global _POPULATED
    if _POPULATED:
        return
    _POPULATED = True
    from . import (  # noqa: F401
        bsr, coo, csr, dia, ell, hybrid, jds, matrix_free, sell, slab)


# ---------------------------------------------------------------------------
# registration
# ---------------------------------------------------------------------------


def _probe_ok(matrix, ctx) -> Capability:
    return CAP_OK


def compiled_probe(base_probe):
    """Compose a probe with the compiled-Pallas platform gate (applied by
    ``register_kernel`` to every ``pallas`` entry)."""

    def probe(matrix, ctx) -> Capability:
        if not on_tpu():
            return Capability(False, "pallas (compiled) needs a TPU backend; "
                                     "use pallas_interpret off-TPU")
        return base_probe(matrix, ctx)

    return probe


#: backends that exist to validate the compiled paths off the chip: the
#: Pallas interpreter and the loop oracles.  On a TPU they are never an
#: auto pick nor a degrade target — a plan that reports one there would
#: hide the device behind a host-speed executor.
HOST_ONLY_BACKENDS = ("pallas_interpret", "loop_reference")


#: why the compiled gather kernels (SELL, CSR row-split) are refused on TPU
GATHER_UNSUPPORTED = ("gathers x with a 1-D jnp.take from VMEM, which "
                      "Mosaic does not lower on TPU (only 2-D gathers)")


def host_only_probe(base_probe):
    """Compose a probe with the host-only gate (applied by
    ``register_kernel`` to every :data:`HOST_ONLY_BACKENDS` entry)."""

    def probe(matrix, ctx) -> Capability:
        if on_tpu():
            return Capability(False, "the interpreter and loop oracles "
                                     "validate off-TPU; on a TPU the "
                                     "compiled entries run")
        return base_probe(matrix, ctx)

    return probe


def _operand_value_dtype(matrix) -> str | None:
    """Canonical value-dtype name of a format-container operand, or None
    for operands without a stored value array (slab metas, placeholders)."""
    if matrix is None:
        return None
    try:
        from ..core import formats as F
        return F.container_value_dtype(matrix)
    except TypeError:
        return None


def dtype_gated_probe(base_probe, value_dtypes: tuple):
    """Wrap a probe with the per-entry value-dtype capability gate."""

    def probe(matrix, ctx) -> Capability:
        name = _operand_value_dtype(matrix)
        if name is not None and name not in value_dtypes:
            return Capability(
                False, f"value dtype {name} unsupported here "
                       f"(supported: {', '.join(value_dtypes)})")
        return base_probe(matrix, ctx)

    return probe


def _probe_pallas_dtype(matrix, ctx) -> Capability:
    import numpy as np
    val = getattr(matrix, "val", None)
    if val is None:
        val = getattr(matrix, "vals", getattr(matrix, "blocks",
                      getattr(matrix, "data", None)))
    if val is not None and np.asarray(val).dtype == np.float64:
        return Capability(False, "TPU Pallas kernels support f32/bf16, not f64")
    return CAP_OK


def default_cost(fmt: str, stream_backend: str, backend: str | None = None):
    """Cost hook factory: the execution-aware roofline of ``perfmodel``
    with the entry's backend-specific stream-byte accounting.

    ``stream_backend`` picks the byte regime (flat vs padded SELL views);
    ``backend`` (the registry backend, defaulting to ``stream_backend``)
    picks the execution-mode derate — the interpreter and the loop oracles
    must never win an auto ranking against a real backend.
    """

    def cost(matrix, ctx: KernelContext) -> float:
        from ..core import perfmodel as PM
        # dtype-honest default: with no explicit access model in the ctx,
        # charge value bytes at the container's actual stored dtype
        am = ctx.am if ctx.am is not None else PM.access_model_for(matrix)
        balance = PM.balance_of(matrix, am, backend=stream_backend)
        eff = PM.exec_efficiency(ctx.chip).get(fmt, 1.0)
        eff *= _BACKEND_DERATE.get(backend or stream_backend, 1.0)
        nnz = max(1, matrix.nnz)
        return PM.predict_exec(fmt, balance, nnz, chip=ctx.chip,
                               efficiency={fmt: eff}).time_s

    return cost


def register(entry: KernelEntry) -> KernelEntry:
    if entry.op not in OPS:
        raise ValueError(f"unknown op {entry.op!r}; expected one of {OPS}")
    if entry.backend not in BACKENDS:
        raise ValueError(f"unknown backend {entry.backend!r}; "
                         f"expected one of {BACKENDS}")
    if entry.key in _TABLE:
        raise ValueError(f"kernel {entry.key} already registered")
    _TABLE[entry.key] = entry
    return entry


def register_kernel(format: str, op: str, backend: str, *, probe=None,
                    cost=None, autotune=None, auto: bool = True,
                    description: str = "", value_dtypes: tuple | None = None):
    """Decorator form: the decorated function is the entry's build hook."""

    def deco(build):
        if probe is not None:
            pr = probe
        elif backend in ("pallas", "pallas_interpret"):
            pr = _probe_pallas_dtype
        else:
            pr = _probe_ok
        if backend == "pallas":
            pr = compiled_probe(pr)
        elif backend in HOST_ONLY_BACKENDS:
            pr = host_only_probe(pr)
        if value_dtypes is not None:
            vd = tuple(value_dtypes)
        elif backend in ("pallas", "pallas_interpret"):
            vd = PALLAS_VALUE_DTYPES
        else:
            vd = ALL_VALUE_DTYPES
        stream = "pallas" if backend in ("pallas", "pallas_interpret") else backend
        register(KernelEntry(
            format=format, op=op, backend=backend, build=build,
            probe=dtype_gated_probe(pr, vd),
            cost=cost if cost is not None else default_cost(format, stream,
                                                            backend),
            autotune=autotune, auto=auto, description=description,
            value_dtypes=vd,
        ))
        return build

    return deco


# ---------------------------------------------------------------------------
# lookup + selection
# ---------------------------------------------------------------------------


def entries(format: str | None = None, op: str | None = None,
            backend: str | None = None) -> list[KernelEntry]:
    """Registered entries, optionally filtered, in registration order."""
    _ensure_populated()
    return [e for e in _TABLE.values()
            if (format is None or e.format == format)
            and (op is None or e.op == op)
            and (backend is None or e.backend == backend)]


def get(format: str, op: str, backend: str) -> KernelEntry:
    _ensure_populated()
    try:
        return _TABLE[(format, op, backend)]
    except KeyError:
        have = sorted(e.backend for e in entries(format, op))
        raise KeyError(
            f"no kernel registered for ({format}, {op}, {backend}); "
            f"registered backends for ({format}, {op}): {have}") from None


def has(format: str, op: str, backend: str) -> bool:
    _ensure_populated()
    return (format, op, backend) in _TABLE


def capabilities(matrix, format: str, op: str,
                 ctx: KernelContext | None = None) -> dict:
    """{backend: Capability} over every entry registered for (format, op)."""
    ctx = ctx or KernelContext()
    return {e.backend: e.probe(matrix, ctx) for e in entries(format, op)}


def build(matrix, format: str, op: str, backend: str,
          ctx: KernelContext | None = None) -> CompiledKernel:
    """Build the executor for an explicit entry; raises
    :class:`BackendUnavailable` when its probe rejects the operand."""
    ctx = ctx or KernelContext()
    entry = get(format, op, backend)
    cap = entry.probe(matrix, ctx)
    if not cap.ok:
        raise BackendUnavailable(
            f"({format}, {op}, {backend}) cannot run here: {cap.reason}")
    return entry.build(matrix, ctx)


def select_backend(matrix, format: str, op: str,
                   ctx: KernelContext | None = None,
                   allowed=None) -> tuple[str, dict]:
    """``backend="auto"``: probe every eligible entry, rank survivors by the
    cost hook (``perfmodel.predict_exec`` seconds), memoize on the container.

    With ``ctx.tuning`` set (a ``core.tunedb.TuneDB``), a fresh measured
    winner recorded for this matrix under ``format`` decides first (the
    warm path); the cost-hook ranking remains the cold fallback and is
    bitwise-identical to the tuning-free behavior.

    Returns ``(backend, {backend: predicted_seconds})``.  Raises
    :class:`BackendUnavailable` if nothing survives the probes.
    """
    ctx = ctx or KernelContext()
    am = ctx.access_model()
    # tiling overrides and the full access model are part of the key: probes
    # depend on the former (a VMEM re-claim for an overridden block can flip
    # a survivor) and costs on the latter, so a choice memoized for one ctx
    # must not answer another (AccessModel is a frozen dataclass: hashable).
    # The tuning DB's identity token is part of the key too: a choice
    # warmed by one DB must not answer for another (or for no DB).
    memo_key = (format, op, ctx.chip.name, am,
                ctx.chunk_block, ctx.width_block, ctx.tile,
                getattr(ctx.tuning, "token", None),
                tuple(sorted(allowed)) if allowed is not None else None)
    memo = getattr(matrix, "_backend_choices", None)
    if memo is None:
        memo = {}
        try:
            object.__setattr__(matrix, "_backend_choices", memo)
        except AttributeError:  # non-dataclass operands: no memo, still works
            memo = None
    if memo is not None and memo_key in memo:
        return memo[memo_key]
    if ctx.tuning is not None:
        tuned = ctx.tuning.lookup_backend(matrix, format, op, chip=ctx.chip)
        if tuned is not None and (allowed is None or tuned["backend"] in allowed):
            # report the *measured* seconds in the cost slot: the warm
            # choice is a measurement, not a prediction
            choice = (tuned["backend"], {tuned["backend"]: tuned["t_measured_s"]})
            if memo is not None:
                memo[memo_key] = choice
            return choice
    costs = {}
    for e in entries(format, op):
        if not e.auto:
            continue
        if allowed is not None and e.backend not in allowed:
            continue
        if not e.probe(matrix, ctx).ok:
            continue
        costs[e.backend] = e.cost(matrix, ctx)
    if not costs:
        raise BackendUnavailable(
            f"no registered backend can run ({format}, {op}) on this "
            f"platform ({jax.default_backend()})")
    # on equal predicted cost the compiled kernel wins: the XLA formulation
    # is the fallback (only a TPU ever offers both)
    choice = (min(costs, key=lambda b: (costs[b], b != "pallas")), costs)
    if memo is not None:
        memo[memo_key] = choice
    return choice


def build_best(matrix, format: str, op: str,
               ctx: KernelContext | None = None, allowed=None) -> CompiledKernel:
    """``select_backend`` + ``build`` in one call."""
    ctx = ctx or KernelContext()
    backend, _ = select_backend(matrix, format, op, ctx, allowed=allowed)
    return build(matrix, format, op, backend, ctx)


# ---------------------------------------------------------------------------
# introspection / CLI (the CI kernel-matrix step)
# ---------------------------------------------------------------------------


def table_rows() -> list[dict]:
    """One row per registered entry: key, auto flag, platform probe, docs.

    The platform probe runs with ``matrix=None`` — entries whose probes
    need a concrete operand report the platform-independent verdict.
    """
    _ensure_populated()
    ctx = KernelContext()
    rows = []
    for e in _TABLE.values():
        try:
            cap = e.probe(None, ctx)
        except (AttributeError, TypeError):
            # operand-dependent probe poking the None placeholder: platform
            # verdict unknown, report "maybe".  Anything else is a probe
            # bug and must surface (probes are contractually never-raise).
            cap = Capability(True, "operand-dependent")
        cost_name = getattr(e.cost, "__name__", "cost")
        rows.append({
            "format": e.format, "op": e.op, "backend": e.backend,
            "auto": e.auto, "available": cap.ok,
            "reason": cap.reason, "description": e.description,
            "value_dtypes": e.value_dtypes,
            # the default hook is a closure out of default_cost; a custom
            # hook reports its own function name
            "cost": ("roofline" if "default_cost"
                     in getattr(e.cost, "__qualname__", "") else cost_name),
            "autotune": (getattr(e.autotune, "__name__", "autotune")
                         if e.autotune is not None else "-"),
        })
    return rows


def format_table(markdown: bool = False) -> str:
    rows = table_rows()
    head = ("format", "op", "backend", "auto", "available", "dtypes",
            "cost", "autotune", "description")
    data = [[r["format"], r["op"], r["backend"],
             "yes" if r["auto"] else "no",
             "yes" if r["available"] else f"no ({r['reason']})",
             ",".join(r["value_dtypes"]),
             r["cost"], r["autotune"],
             r["description"]] for r in rows]
    widths = [max([len(h)] + [len(str(row[i])) for row in data])
              for i, h in enumerate(head)]
    sep = " | " if markdown else "  "
    lines = []
    lines.append(sep.join(h.ljust(w) for h, w in zip(head, widths)))
    if markdown:
        lines[0] = "| " + lines[0] + " |"
        lines.append("| " + " | ".join("-" * w for w in widths) + " |")
    for row in data:
        line = sep.join(str(c).ljust(w) for c, w in zip(row, widths))
        lines.append(("| " + line + " |") if markdown else line)
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Inspect the unified kernel registry")
    ap.add_argument("--list", action="store_true",
                    help="print the registered (format, op, backend) table")
    ap.add_argument("--markdown", action="store_true",
                    help="emit a GitHub-flavored markdown table "
                         "(for $GITHUB_STEP_SUMMARY)")
    args = ap.parse_args(argv)
    if args.list or args.markdown:
        n = len(table_rows())
        backends = sorted({r["backend"] for r in table_rows()})
        if args.markdown:
            print(f"### Kernel registry — {n} entries "
                  f"({len(backends)} backends) on "
                  f"`{jax.default_backend()}`\n")
        print(format_table(markdown=args.markdown))
        return 0
    ap.print_help()
    return 0


if __name__ == "__main__":
    # ``python -m repro.kernels.registry`` executes this file as __main__
    # while the package import created the canonical module (where every
    # kernel registered).  Delegate to that instance — its table, not the
    # empty one runpy would otherwise see.
    from repro.kernels import registry as _canonical

    sys.exit(_canonical.main(sys.argv[1:]))
