"""Build-once host-preprocessing cache shared by every kernel module.

Host-derived metadata (CSR row ids, JDS segment tables, SELL padded views,
DIA Pallas padding, row-split slabs) is computed **once per container**
and pinned on the (frozen) dataclass via ``object.__setattr__`` — repeated
SpMV calls on the same matrix never redo preprocessing.  Each build counts
one under ``precompute.<kind>`` in ``utils.spans``' counter table;
``precompute_stats`` is the view of those counters that tests read to
assert no recomputation (the plan layer's contract).
"""
from __future__ import annotations

import jax
import numpy as np

from ..utils import spans

_STAT = "precompute."   # prefix of the build counters in utils.spans


def register_stat(name: str) -> str:
    """Declare a build counter (idempotent); returns the name for reuse.
    Kernel modules declare their kinds at import."""
    spans.count(_STAT + name, 0)
    return name


def precompute_stats() -> dict:
    """``{kind: builds}`` of the host-preprocessing build counters."""
    return {k[len(_STAT):]: n for k, n in spans.snapshot()["counters"].items()
            if k.startswith(_STAT)}


def cached(m, attr: str, stat: str, build):
    """Build-once metadata cached on the frozen container (not a pytree
    field, so jit boundaries and tree_map never see it).

    Builders must return concrete *numpy* arrays: the first SpMV call may
    happen inside a jit trace, and caching a ``jnp`` value created there
    would leak a tracer into later traces.  Device placement happens at the
    use site (a constant-embed under jit, or once at plan compile time).
    """
    out = getattr(m, attr, None)
    if out is None:
        spans.count(_STAT + stat)
        out = build()
        object.__setattr__(m, attr, out)
    return out


def is_traced(a) -> bool:
    return isinstance(a, jax.core.Tracer)


def to_device(m, *arrays) -> tuple:
    """The device copies of a build's operands (``None`` stays ``None``):
    the arrays a ``CompiledKernel`` receives as jit arguments.  Copies are
    kept on the container ``m``, so each host array is put on the device
    once however many executors (SpMV, SpMM, several plans) read it.
    A build traced inside ``jax.jit`` still gets concrete copies (never
    tracers that would outlive the trace in the store)."""
    import jax
    import jax.numpy as jnp

    store = getattr(m, "_device_copies", None)
    if store is None:
        store = {}
        object.__setattr__(m, "_device_copies", store)
    out = []
    with jax.ensure_compile_time_eval():
        for a in arrays:
            if a is None or not isinstance(a, np.ndarray):
                out.append(None if a is None else jnp.asarray(a))
                continue
            hit = store.get(id(a))
            if hit is None or hit[0] is not a:
                hit = store[id(a)] = (a, jnp.asarray(a))
            out.append(hit[1])
    return tuple(out)


def spmm_by_columns(spmv_fn):
    """Lift an SpMV to the SpMM contract column by column.

    ``spmv_fn(*head, x)`` — a closure ``f(x)`` or a kernel ``f(operands,
    x)``; the lifted function takes the same leading arguments and an
    ``(N, K)`` block.  The loop-reference oracle for multi-vector ops: K
    separate SpMVs, stacked.  Obviously correct, and independent of every
    fused SpMM formulation it is used to validate.
    """
    import jax.numpy as jnp

    def f(*args):
        *head, X = args
        return jnp.stack([spmv_fn(*head, X[:, j]) for j in range(X.shape[1])],
                         axis=1)

    return f
