"""Named spans and counters: the program's own record of where time goes.

``span(name)`` enters ``jax.profiler.TraceAnnotation(name)``, so that a
running profiler puts the span on its host timeline beside the device's
programs, and on exit adds the span's wall time (``time.perf_counter``)
and a count of one to an in-process total under ``name``, also when the
body raises.  ``count(name, n)`` adds ``n`` to a counter.  ``snapshot()``
copies both tables.  The totals see work done while no profiler runs,
such as plan set-up.

A span reads the host clock twice and takes one uncontended lock: it never
waits on the device, moves data or reorders a dispatch.

Names in use (dots separate a layer from its part):

- ``lanczos``, ``lanczos.step``, ``lanczos.sync`` (``core.eigensolver``):
  one solve attempt, one iteration, and the host's blocking reads of the
  recurrence coefficients.
- ``pagerank``, ``pagerank.step``, ``pagerank.sync`` (``core.eigensolver``):
  one PageRank solve, one iteration, and the host's blocking read of the
  iteration's L1 change.
- ``plan.select``, ``plan.convert``, ``plan.build`` (``core.plan``): format
  and backend selection, format conversion, executor builds with their
  tables and device copies.  They never nest in one another.
- counters ``precompute.<kind>`` (``kernels.cache``) and
  ``pack.shard_packs``, ``pack.format_selections``
  (``core.distributed_plan``): host preprocessing builds.
- counter ``dia.shifted_slices`` (``kernels.dia``): DIA containers whose
  diagonals the XLA executors stream as shifted slices of x, once each.
"""
from __future__ import annotations

import threading
import time

import jax

_LOCK = threading.Lock()
_SPANS: dict[str, list] = {}      # name -> [count, seconds]
_COUNTERS: dict[str, int] = {}


class span:
    """``with span(name):`` a profiler annotation and an in-process total."""

    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        with _LOCK:
            tot = _SPANS.get(self.name)
            if tot is None:
                tot = _SPANS[self.name] = [0, 0.0]
            tot[0] += 1
            tot[1] += dt
        return False


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` (``n=0`` declares it)."""
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + n


def snapshot() -> dict:
    """``{"spans": {name: [count, seconds]}, "counters": {name: n}}``, a copy."""
    with _LOCK:
        return {"spans": {k: list(v) for k, v in _SPANS.items()},
                "counters": dict(_COUNTERS)}
