"""Micro-batching primitives: futures, per-operator queues, the coalescer.

The paper's bound (Secs. 3-5) is per-*pass*: one SpMV streams the whole
matrix and saturates at BW / balance no matter how many cores push on it.
The only way a serving layer beats that ceiling is to stop paying the
matrix stream once per request — gather k concurrent ``y = A @ x`` requests
for the same operator and execute them as a single ``plan.spmm(X)``, which
streams the matrix once for all k (``perfmodel.spmm_balance_of``).

This module holds the mechanism; the policy (which width, which deadline)
and the operator registry live in ``serve.engine.BatchingSpMVServer``.
Everything is cooperative and single-threaded: batches are flushed by
``submit`` (width reached / deadline elapsed), by ``pump()``, or by a
consumer demanding a ``result()`` — deterministic by construction, which is
what the tests and the injectable ``clock`` rely on.
"""
from __future__ import annotations

import functools
from collections import deque
from dataclasses import dataclass

import jax
import jax.numpy as jnp


class BackpressureError(RuntimeError):
    """Raised when an operator's pending queue is at its ``max_pending`` cap.

    The cap bounds queue memory under open-loop overload: shedding the
    request at submission time is the only backpressure signal a cooperative
    (thread-free) batcher can give its callers.
    """


class SpMVFuture:
    """Handle for one submitted request; resolves when its batch executes.

    ``result()`` never deadlocks: if the batch is still pending (width not
    reached, deadline not elapsed), it forces a flush of the owning
    operator queue — a consumer demanding an answer outranks the policy.

    A future can resolve with a *structured error* instead of a value (one
    poisoned request must not fail its batch-mates — see
    ``serve.resilience``): ``done()`` is then still True, ``error()``
    returns the carried exception, and ``result()`` raises it.
    """

    __slots__ = ("_queue", "_value", "_error", "_done", "_check")

    def __init__(self, queue: "OperatorQueue"):
        self._queue = queue
        self._value = None
        self._error = None
        self._done = False
        self._check = None  # deferred finiteness verdict: (shared, column)

    def done(self) -> bool:
        """True once the owning batch has executed (value OR error)."""
        return self._done

    def error(self) -> BaseException | None:
        """The structured error this request failed with, or None."""
        if not self._done:
            self._queue.flush()
        self._materialize()
        return self._error

    def result(self) -> jnp.ndarray:
        """The request's ``y = A @ x`` column, flushing its batch if needed.

        Raises the request's structured error (``RequestError`` subclass —
        ``KernelFault``, ``DeadlineExceeded``) when the request failed.
        """
        if not self._done:
            self._queue.flush()
        self._materialize()
        if self._error is not None:
            raise self._error
        return self._value

    def _materialize(self) -> None:
        """Settle a deferred finiteness verdict (see ``_resolve_checked``).

        The batch-wide verdict vector is synced exactly once — by the first
        consumer, who has to wait for the device anyway — and shared with
        every batch-mate; a non-finite column flips this future to a
        ``KernelFault`` and does the stats/breaker bookkeeping the flush
        deferred.
        """
        if self._check is None:
            return
        shared, i = self._check
        self._check = None
        if shared["host"] is None:
            import numpy as np
            shared["host"] = np.asarray(shared["vec"])
        if not shared["host"][i]:
            from .resilience import KernelFault
            queue = shared["queue"]
            self._value = None
            self._error = KernelFault(
                "batch column came back non-finite (kernel fault, or a "
                "NaN/Inf request that bypassed validation)",
                op="spmm", kernel=shared["kernel"], nonfinite=True)
            queue.stats.failed += 1
            queue.breaker.record_failure()

    def _resolve(self, value: jnp.ndarray) -> None:
        self._value = value
        self._done = True
        self._queue = None  # drop the back-reference once resolved

    def _resolve_checked(self, value: jnp.ndarray, shared: dict, i: int) -> None:
        """Resolve with a batch-shared, not-yet-synced finiteness verdict.

        ``shared`` holds the device-side per-column verdict of this
        future's batch (``{"vec", "host", "queue", "kernel"}``); syncing it
        at flush time would cost the hot path a device round-trip per
        batch, so the sync rides on the first ``result()``/``error()``
        instead — consumers pay nothing they would not already pay to read
        the value.
        """
        self._value = value
        self._check = (shared, i)
        self._done = True
        self._queue = None

    def _fail(self, error: BaseException) -> None:
        self._error = error
        self._done = True
        self._queue = None


@dataclass(frozen=True)
class BatchPolicy:
    """When to flush an operator's queue, and how to shape partial batches.

    Attributes:
        width: flush as soon as this many requests are queued.  The serving
            layer derives it from the SpMM roofline
            (``perfmodel.select_batch_width``) unless overridden.
        deadline_s: flush when the *oldest* queued request has waited this
            long — bounds latency when traffic is too thin to fill a batch.
        pad_to_width: execute partial batches padded with zero columns up to
            ``width`` so the jitted ``spmm`` only ever sees one shape (no
            per-width retrace); the padding is accounted in the stats.
        max_pending: queue-length cap; ``submit`` raises
            ``BackpressureError`` beyond it.
    """

    width: int
    deadline_s: float = 1e-3
    pad_to_width: bool = True
    max_pending: int = 256


@dataclass
class QueueStats:
    """Per-operator serving counters (the ``stats()`` satellite).

    ``calls`` counts *queries answered* (batched requests + direct
    spmv/spmm calls); padding columns are streamed work, not queries, so
    they appear only in ``padding_ratio``.
    """

    requests: int = 0          # submitted through the batcher
    calls: int = 0             # queries answered (batched + direct paths)
    batches: int = 0           # spmm flushes executed
    batched_columns: int = 0   # real columns across all flushes
    padded_columns: int = 0    # zero columns streamed for shape stability
    fast_path_calls: int = 0   # width-1 submits executed as plan(x)
    shed: int = 0              # rejected at submit (backpressure cap)
    retried: int = 0           # batch re-executions (transient faults)
    degraded: int = 0          # backend-ladder steps taken by the breaker
    deadline_missed: int = 0   # requests shed with DeadlineExceeded
    failed: int = 0            # requests resolved with a structured error

    def record_batch(self, k: int, n_pad: int = 0) -> None:
        """Account one executed batch of k real columns (+ n_pad zeros) —
        the single bookkeeping point for batcher flushes and direct spmm."""
        self.batches += 1
        self.batched_columns += k
        self.padded_columns += n_pad
        self.calls += k

    @property
    def mean_batch_width(self) -> float:
        """Mean *real* (unpadded) width over executed batches."""
        return self.batched_columns / self.batches if self.batches else 0.0

    @property
    def padding_ratio(self) -> float:
        """Padded columns / streamed columns (0.0 = every column was real)."""
        streamed = self.batched_columns + self.padded_columns
        return self.padded_columns / streamed if streamed else 0.0


def coalesce(xs: list, width: int, pad_to_width: bool) -> tuple[jnp.ndarray, int]:
    """Stack k request vectors into one SpMM operand.

    Args:
        xs: k vectors of shape (n,), the queued requests in arrival order.
        width: the policy width to pad up to.
        pad_to_width: whether partial batches get zero columns appended.

    Returns:
        (X, n_pad): X of shape (n, k + n_pad) with requests as columns.
    """
    X = jnp.stack(xs, axis=1)
    n_pad = width - len(xs) if (pad_to_width and len(xs) < width) else 0
    if n_pad:
        X = jnp.pad(X, ((0, 0), (0, n_pad)))
    return X, n_pad


class OperatorQueue:
    """Pending requests for one registered operator + its flush machinery.

    Holds the compiled plan (``SpMVPlan`` or ``DistributedSpMVPlan`` — both
    expose ``spmv``/``spmm``), the flush policy, the stats counters, and
    the robustness state: the request-validation policy, the resilience
    policy + circuit breaker, and the backend degradation ladder
    (``rebuild(backend)`` recompiles the operator one rung down when the
    breaker trips — see ``serve.resilience``).
    """

    def __init__(self, plan, policy: BatchPolicy, clock, *,
                 validate: str = "off", resilience=None,
                 rebuild=None, ladder=()):
        from .resilience import CircuitBreaker, ResiliencePolicy
        self.plan = plan
        self.policy = policy
        self._clock = clock
        self._validate = validate
        self.resilience = resilience if resilience is not None else (
            ResiliencePolicy())
        self._rebuild = rebuild
        self.ladder = list(ladder)
        self.breaker = CircuitBreaker(self.resilience.breaker_threshold)
        self._n_cols = int(plan.report.shape[1])
        self._pending: deque = deque()  # (x, future, t_enqueue, timeout_s)
        self._executors: dict = {}      # real width k -> jitted batch fn
        self.stats = QueueStats()

    def __len__(self) -> int:
        return len(self._pending)

    # -- submission ---------------------------------------------------------

    def submit(self, x: jnp.ndarray, *, timeout_s: float | None = None) -> SpMVFuture:
        """Enqueue one request; flush if the policy says the batch is due.

        ``timeout_s`` overrides the resilience policy's per-request
        deadline for this request (None keeps the policy default).
        """
        from ..core.validate import validate_vector
        from ..testing import faults
        # reject bad requests at the offending caller — a bad shape (or,
        # under validate="strict", a NaN/Inf payload) reaching flush would
        # poison the whole batch and strand its valid futures.  When the
        # resilient flush already runs the fused per-column finiteness
        # check, the strict per-request sync (one device round-trip per
        # submit — the dominant guardrail cost) is deferred to it: a
        # non-finite request then fails its own future at flush instead of
        # raising here, and its batch-mates still resolve.
        defer = (self.policy.width > 1 and self.resilience.enabled
                 and self.resilience.check_finite)
        x = validate_vector(x, self._n_cols, policy=self._validate,
                            defer_finite=defer)
        self.stats.requests += 1
        if self.policy.width <= 1:
            # fast path: a width-1 policy means batching cannot amortize
            # anything — execute exactly what plan(x) would, synchronously
            fut = SpMVFuture(self)
            fut._resolve(self.plan.spmv(x))
            self.stats.fast_path_calls += 1
            self.stats.calls += 1
            return fut
        try:
            faults.fire("serve.queue_full", ctx={"pending": len(self._pending)},
                        clock=self._clock)
            full = len(self._pending) >= self.policy.max_pending
        except BackpressureError:
            full = True
        if full:
            self.stats.requests -= 1  # shed: the request was not admitted
            self.stats.shed += 1
            raise BackpressureError(
                f"{len(self._pending)} pending requests at the "
                f"max_pending={self.policy.max_pending} cap; drain with "
                f"pump()/flush() or raise the cap")
        fut = SpMVFuture(self)
        self._pending.append((x, fut, self._clock(), timeout_s))
        if len(self._pending) >= self.policy.width or self._deadline_elapsed():
            self.flush()
        return fut

    # -- flushing -----------------------------------------------------------

    def _deadline_elapsed(self) -> bool:
        if not self._pending:
            return False
        return self._clock() - self._pending[0][2] >= self.policy.deadline_s

    def due(self) -> bool:
        """True when the policy wants a flush (width reached or deadline)."""
        return (len(self._pending) >= self.policy.width
                or self._deadline_elapsed())

    def _splitter(self, k: int, check: bool = False):
        """Jitted Y -> (Y[:,0], ..., Y[:,k-1]) column split, cached per k.

        One dispatch to hand each future its column, instead of k eager
        slice ops (which cost more than the SpMM itself at paper scale).
        At most ``policy.width`` distinct k's exist, so the cache is
        bounded.  The stack/pad stays *eager* on purpose: fusing it into
        the spmm graph makes XLA re-materialize the stacked operand inside
        the gather and roughly doubles the batch time.

        ``check=True`` prepends a per-column all-finite verdict to the
        return value, fused into the same compiled call; the resilient
        flush hands the un-synced verdict to the futures, whose first
        consumer materializes it (``SpMVFuture._materialize``) — the
        no-silent-NaN guarantee costs one fused reduction and zero extra
        device round-trips.
        """
        key = (k, check)
        fn = self._executors.get(key)
        if fn is None:
            if check:
                fn = jax.jit(lambda Y: (
                    jnp.all(jnp.isfinite(Y[:, :k]), axis=0),
                    tuple(Y[:, i] for i in range(k))))
            else:
                fn = jax.jit(lambda Y: tuple(Y[:, i] for i in range(k)))
            self._executors[key] = fn
        return fn

    def _fused(self, k: int):
        """Jitted X -> (verdict, columns) with the *spmm inlined*: one
        compiled program for execute + per-column finiteness + split.

        ``plan.kernel_multi`` is itself a jitted callable, so tracing it
        here inlines the kernel and lets XLA fuse the ``isfinite``
        reduction and the column copies into the spmm's own output pass —
        the no-silent-NaN guarantee becomes close to free, which is what
        keeps the guardrails-overhead gate (``check_bench --bound``)
        honest.  The plan's operands enter as arguments, so the program
        built per batch width never embeds the matrix.  Only local ``SpMVPlan``s take this path (distributed
        plans keep their own fault points and collectives observable);
        the resilience layer also skips it whenever a fault is armed on
        ``plan.spmm``, so chaos tests still drive the exact production
        wrapper.  Returns None when fusion is unavailable.
        """
        key = (k, "fused")
        fn = self._executors.get(key)
        if fn is None:
            from ..core.plan import SpMVPlan
            if isinstance(self.plan, SpMVPlan):
                inner, ops = self.plan.kernel_multi, self.plan.operands_multi

                def run(ops, X, _inner=inner, _k=k):
                    Y = _inner(ops, X)
                    return (jnp.all(jnp.isfinite(Y[:, :_k]), axis=0),
                            tuple(Y[:, i] for i in range(_k)))
                fn = functools.partial(jax.jit(run), ops)
            else:
                fn = False  # cache the miss; cleared on degrade()
            self._executors[key] = fn
        return fn or None

    def flush(self) -> int:
        """Execute all pending requests as one (padded) SpMM; resolve futures.

        The execution itself is delegated to the resilience layer
        (``serve.resilience.execute_flush``): every drained future resolves
        with a value or a structured error; with resilience disabled the
        legacy behavior (exceptions propagate, batch stranded) applies.

        Returns:
            The number of real requests answered (0 if the queue was empty).
        """
        from .resilience import execute_flush
        if not self._pending:
            return 0
        entries = []
        while self._pending:
            entries.append(self._pending.popleft())
        return execute_flush(self, entries)

    # -- degradation ---------------------------------------------------------

    def degrade(self) -> bool:
        """Step the operator one rung down its backend ladder.

        Called by the resilience layer when the circuit breaker trips.
        Recompiles the plan on the next ladder backend (via the ``rebuild``
        closure the server registered), drops the cached splitters (their
        captured dtypes may change), and resets the breaker so the new
        backend gets a full failure budget.

        Returns:
            True when a degrade happened; False when the ladder is empty
            or the operator was registered without a rebuild hook.
        """
        if not self.ladder or self._rebuild is None:
            return False
        backend = self.ladder.pop(0)
        try:
            self.plan = self._rebuild(backend)
        except Exception:  # noqa: BLE001 - a rung that fails to build is skipped
            return self.degrade()
        self._executors.clear()
        self.stats.degraded += 1
        self.breaker.failures = 0
        return True
