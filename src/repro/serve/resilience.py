"""Resilience for the serving stack: structured errors, deadlines, retry
with poison isolation, and a circuit breaker that degrades the backend.

The batching layer (``serve.batching``) coalesces k requests into one SpMM,
which makes the failure domain k requests wide: an unstructured kernel
exception mid-flush used to strand every future in the batch.  This module
shrinks the failure domain back to one request:

* **Structured errors** — :class:`RequestError` and its subclasses are
  *carried on the future* (``SpMVFuture.result()`` re-raises them), so one
  bad request reports its own failure and its batch-mates resolve normally.
* **Deadline-aware shedding** — a request older than
  ``ResiliencePolicy.request_timeout_s`` at flush time is resolved with
  :class:`DeadlineExceeded` instead of being executed: under overload,
  computing an answer nobody is waiting for anymore wastes the very
  bandwidth the batcher exists to protect.
* **Retry with split** — a flush whose kernel *raises* is retried
  (``max_retries``, with ``retry_backoff_s`` waited through the injectable
  clock); if it still fails and the batch has >1 request, it is split in
  half and each half retried independently — O(log k) extra executions
  isolate a poison request while every healthy request still gets its
  answer.  A persistent single-request failure becomes a
  :class:`KernelFault` on exactly that future.
* **Non-finite isolation** — after a successful execution the batch result
  is checked per column (one fused reduction, computed with the column
  split in a single compiled call and synced lazily by the first consumer
  — the flush itself pays no device round-trip); poisoned columns (a
  kernel writing NaN, or a non-finite input that bypassed validation) fail
  their own future with :class:`KernelFault` and never propagate silently.
* **Circuit breaker + degradation ladder** — ``breaker_threshold``
  consecutive kernel failures trip the operator's breaker, which recompiles
  its plan one step down the backend ladder (``pallas -> xla ->
  loop_reference``, filtered through the kernel registry's capability
  probes; on a TPU only ``pallas -> xla`` — the interpreter and the loop
  oracles are host-only).  A tripped-and-degraded operator retries immediately on the new
  backend; the ladder is finite, so so is the recovery loop.

Everything here is cooperative and synchronous, like the batcher it guards:
no threads, no wall-clock sleeps in tests (backoff goes through the
injectable clock), deterministic by construction.
"""
from __future__ import annotations

from dataclasses import dataclass

from ..testing import faults


class RequestError(RuntimeError):
    """Base of per-request serving failures carried on an ``SpMVFuture``."""


class KernelFault(RequestError):
    """The kernel raised, or produced a non-finite result, for this request.

    Attributes:
        op: "spmv" | "spmm" — the executing operation.
        kernel: the plan's kernel label at the time of the fault.
        nonfinite: True when the fault was a NaN/Inf result rather than an
            exception (the exception case chains the cause).
    """

    def __init__(self, message: str, *, op: str = "spmm", kernel: str = "?",
                 nonfinite: bool = False):
        super().__init__(message)
        self.op = op
        self.kernel = kernel
        self.nonfinite = nonfinite


class DeadlineExceeded(RequestError):
    """The request out-waited its deadline and was shed unexecuted.

    Attributes:
        waited_s: how long the request had been queued at flush time.
        timeout_s: the policy deadline it exceeded.
    """

    def __init__(self, waited_s: float, timeout_s: float):
        super().__init__(
            f"request shed after waiting {waited_s:.6f}s "
            f"(> request_timeout_s={timeout_s:.6f}s); it was never executed")
        self.waited_s = waited_s
        self.timeout_s = timeout_s


@dataclass(frozen=True)
class ResiliencePolicy:
    """Per-operator knobs for the resilient flush path.

    Attributes:
        enabled: master switch.  Off, ``flush`` executes the legacy way —
            exceptions propagate and strand the batch (benchmark mode; the
            guardrails-overhead measurement compares against this).
        max_retries: whole-batch re-executions after a kernel exception
            before the batch is split (0 disables the transient-fault
            retry; splitting still isolates poison requests).
        retry_backoff_s: waited through the queue's clock before each
            retry (``clock.advance`` when the clock supports it — the
            injected test clock — otherwise a real sleep).
        breaker_threshold: consecutive failed executions that trip the
            operator's circuit breaker and trigger a backend degrade.
        request_timeout_s: per-request deadline for the shedding check
            (None disables).  Distinct from ``BatchPolicy.deadline_s``,
            which *forces* a flush; this one *abandons* requests that
            already missed their SLO.
        check_finite: per-column finiteness check of every batch result
            (one fused reduction per flush; the verdict syncs on first
            consumption, so the flush adds no device round-trip).
    """

    enabled: bool = True
    max_retries: int = 1
    retry_backoff_s: float = 0.0
    breaker_threshold: int = 3
    request_timeout_s: float | None = None
    check_finite: bool = True


class CircuitBreaker:
    """Consecutive-failure counter with a trip threshold (per operator)."""

    def __init__(self, threshold: int):
        self.threshold = max(1, int(threshold))
        self.failures = 0
        self.trips = 0

    def record_failure(self) -> bool:
        """Count one failed execution; True when this one trips the breaker."""
        self.failures += 1
        if self.failures >= self.threshold:
            self.trips += 1
            self.failures = 0
            return True
        return False

    def record_success(self) -> None:
        self.failures = 0


#: backend quality order for the degradation ladder, best first.  A plan
#: kernel label maps into this list; everything strictly below it is a
#: legal degrade target (filtered through the registry probes).
_LADDER = ("pallas", "pallas_interpret", "xla", "loop_reference")

#: plan-report kernel label -> ladder position name
_LABEL_TO_BACKEND = {"pallas": "pallas", "pallas-interpret": "pallas_interpret",
                     "xla": "xla", "loop": "loop_reference"}


def degradation_ladder(fmt: str, kernel_label: str, matrix=None) -> list[str]:
    """Registry backends strictly below ``kernel_label`` for ``fmt``, best
    first — the operator's remaining degrade steps.

    Filtered to entries that exist and whose capability probe accepts the
    operand (probes never raise; a missing entry simply isn't a rung).
    Distributed plans don't use this — their slab multiplies know exactly
    two backends (xla, loop_reference), see ``engine.register_distributed``.
    """
    from ..kernels import registry as R
    cur = _LABEL_TO_BACKEND.get(kernel_label, "xla")
    below = _LADDER[_LADDER.index(cur) + 1:]
    out = []
    for be in below:
        if not (R.has(fmt, "spmv", be) and R.has(fmt, "spmm", be)):
            continue
        if R.on_tpu() and be in R.HOST_ONLY_BACKENDS:
            continue  # never step from the chip onto a host-speed executor
        if matrix is not None:
            ctx = R.KernelContext()
            if not (R.get(fmt, "spmv", be).probe(matrix, ctx).ok
                    and R.get(fmt, "spmm", be).probe(matrix, ctx).ok):
                continue
        out.append(be)
    return out


def _wait(clock, seconds: float) -> None:
    """Back off through the injectable clock (deterministic in tests)."""
    if seconds <= 0:
        return
    if hasattr(clock, "advance"):
        clock.advance(seconds)
    else:  # real monotonic clock: a genuine (bounded) backoff sleep
        import time
        time.sleep(min(seconds, 0.1))


# ---------------------------------------------------------------------------
# the resilient flush
# ---------------------------------------------------------------------------


def execute_flush(queue, entries: list) -> int:
    """Resolve every drained request of one flush, come what may.

    ``entries`` is the drained pending list ``[(x, future, t_enqueue,
    timeout_override)]``.  Every future is resolved by the time this
    returns — with a value, or with a structured :class:`RequestError` —
    and the return value is the number of requests answered (the legacy
    ``flush`` contract).

    Raises only when the resilience policy is disabled (legacy behavior:
    the exception propagates and the batch is stranded).
    """
    pol = queue.resilience
    clock = queue._clock
    xs = [e[0] for e in entries]
    futs = [e[1] for e in entries]

    if pol is None or not pol.enabled:
        faults.fire("serve.flush", ctx={"k": len(xs)}, clock=clock)
        _resolve_batch(queue, xs, futs, check_finite=False)
        return len(futs)

    # 1. deadline-aware shedding: abandon requests that already missed
    #    their SLO instead of spending a matrix stream on them
    now = clock()
    live_xs, live_futs = [], []
    for x, fut, t0, override in entries:
        limit = override if override is not None else pol.request_timeout_s
        waited = now - t0
        if limit is not None and waited > limit:
            fut._fail(DeadlineExceeded(waited, limit))
            queue.stats.deadline_missed += 1
        else:
            live_xs.append(x)
            live_futs.append(fut)
    xs, futs = live_xs, live_futs
    if not xs:
        return len(entries)

    _run(queue, xs, futs, pol, attempt=0)
    return len(entries)


def _run(queue, xs, futs, pol: ResiliencePolicy, attempt: int) -> None:
    """Execute one (sub-)batch with retry, split, breaker and degrade."""
    try:
        faults.fire("serve.flush", ctx={"k": len(xs)}, clock=queue._clock)
        _resolve_batch(queue, xs, futs, check_finite=pol.check_finite)
        return
    except Exception as e:  # noqa: BLE001 - any kernel/runtime fault
        tripped = queue.breaker.record_failure()
        if tripped and queue.degrade():
            # the world changed (new backend): retry at the same attempt —
            # the ladder is finite, so this cannot loop forever
            queue.stats.retried += 1
            return _run(queue, xs, futs, pol, attempt)
        if attempt < pol.max_retries:
            _wait(queue._clock, pol.retry_backoff_s * (2 ** attempt))
            queue.stats.retried += 1
            return _run(queue, xs, futs, pol, attempt + 1)
        if len(xs) > 1:
            # retries exhausted: split to isolate the poison request; the
            # halves get no fresh whole-batch retries (bounded work)
            mid = len(xs) // 2
            _run(queue, xs[:mid], futs[:mid], pol, attempt=pol.max_retries)
            _run(queue, xs[mid:], futs[mid:], pol, attempt=pol.max_retries)
            return
        fault = KernelFault(
            f"kernel failed for this request after retries: "
            f"{type(e).__name__}: {e}",
            op="spmm", kernel=queue.plan.report.kernel)
        fault.__cause__ = e
        futs[0]._fail(fault)
        queue.stats.failed += 1


def _resolve_batch(queue, xs, futs, *, check_finite: bool) -> None:
    """One actual execution: coalesce, spmm, split+check (fused), resolve."""
    from .batching import coalesce

    k = len(futs)
    X, n_pad = coalesce(xs, queue.policy.width, queue.policy.pad_to_width)
    if check_finite:
        # the per-column verdict and the columns come out of ONE compiled
        # program — for local plans the spmm itself is inlined into it
        # (OperatorQueue._fused), so XLA folds the isfinite reduction into
        # the kernel's output pass and the check is close to free.  The
        # verdict is NOT synced here: each future carries a reference to
        # the shared device-side vector and the first consumer's
        # result()/error() materializes it (see SpMVFuture._materialize)
        # — zero device round-trips on the flush path.  Whenever a fault
        # is armed on the plan's spmm point we drop to the two-program
        # path through queue.plan.spmm so chaos tests drive the exact
        # production wrapper (fire + poison).
        fused = queue._fused(k)
        if fused is not None and faults.armed("plan.spmm") is None:
            ok_dev, cols = fused(X)
        else:
            Y = queue.plan.spmm(X)
            ok_dev, cols = queue._splitter(k, check=True)(Y)
        shared = {"vec": ok_dev, "host": None, "queue": queue,
                  "kernel": queue.plan.report.kernel}
        for i, (fut, y) in enumerate(zip(futs, cols)):
            fut._resolve_checked(y, shared, i)
    else:
        Y = queue.plan.spmm(X)
        cols = queue._splitter(k)(Y)
        for fut, y in zip(futs, cols):
            fut._resolve(y)
    queue.stats.record_batch(k, n_pad)
    queue.breaker.record_success()
