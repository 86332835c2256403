"""Serving engines: token decode waves + micro-batched SpMV operators.

Two serving surfaces share this module because they are the same regime at
two granularities:

* ``Engine`` — prefill + decode waves over a fixed slot batch.  Decode is
  the paper's regime: every step streams all active weights (and the KV
  cache) against one activation vector per slot — a bandwidth-bound MVM
  pipeline.  Requests in a wave share positions (prompts padded to the
  wave's max); new requests are admitted at wave boundaries into freed
  slots (continuous batching at wave granularity).

* ``BatchingSpMVServer`` — the operator-level analogue: concurrent
  ``y = A @ x`` requests against a registered matrix are coalesced into a
  single ``plan.spmm(X)`` so the matrix is streamed once per *batch*
  instead of once per *request* (see ``serve.batching`` for the queue
  machinery and ``perfmodel.select_batch_width`` for the width policy).
  ``SparseOperatorServer`` remains as the direct-call compatibility name.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core import perfmodel as PM
from ..core.plan import SpMVPlan
from ..models.registry import Model
from .batching import BatchPolicy, OperatorQueue, SpMVFuture  # noqa: F401
from .kv_cache import SlotManager, zeros_like_shapes


@dataclass
class GenerationConfig:
    """Sampling knobs for one ``Engine.generate`` wave."""

    max_new_tokens: int = 32
    temperature: float = 0.0         # 0 => greedy
    eos_id: int = -1                 # -1 => never stops early
    seed: int = 0


class Engine:
    """Token serving engine: one jitted prefill + decode step over a fixed
    slot batch (the decode-MVM regime the paper's roofline maps onto)."""

    def __init__(self, model: Model, params, *, batch_size: int, max_len: int):
        self.model = model
        self.params = params
        self.batch_size = batch_size
        self.max_len = max_len
        self.slots = SlotManager(batch_size, max_len)
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step)

    def _sample(self, logits: jnp.ndarray, cfg: GenerationConfig, key):
        if cfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / cfg.temperature, axis=-1).astype(jnp.int32)

    def generate(self, prompts: np.ndarray, cfg: GenerationConfig = GenerationConfig()):
        """Run one synchronized prefill + decode wave.

        Args:
            prompts: (n, prompt_len) int32 token ids, n <= batch_size;
                prompts share positions (pad to the wave's max upstream).
            cfg: sampling configuration for the wave.

        Returns:
            A list of n generated-token lists (ints), one per prompt.
        """
        n, plen = prompts.shape
        assert n <= self.batch_size
        B = self.batch_size
        toks = np.zeros((B, plen), np.int32)
        toks[:n] = prompts
        for r in range(n):
            self.slots.admit(r, plen)

        cache = zeros_like_shapes(self.model.cache_shape(B, self.max_len))
        logits, cache = self._prefill(self.params, {"tokens": jnp.asarray(toks)}, cache)
        key = jax.random.PRNGKey(cfg.seed)
        pos = plen
        outs: list[list[int]] = [[] for _ in range(B)]
        tok = self._sample(logits, cfg, key)
        for i in range(n):
            self.slots.record_token(i, int(tok[i]), cfg.eos_id, cfg.max_new_tokens)
            outs[i].append(int(tok[i]))
        while pos < self.max_len - 1 and self.slots.active_mask()[:n].any():
            key, sub = jax.random.split(key)
            logits, cache = self._decode(self.params, cache, tok, jnp.int32(pos))
            tok = self._sample(logits, cfg, sub)
            pos += 1
            active = self.slots.active_mask()
            for i in range(n):
                if active[i]:
                    self.slots.record_token(i, int(tok[i]), cfg.eos_id, cfg.max_new_tokens)
                    outs[i].append(int(tok[i]))
        return [outs[i] for i in range(n)]

    # --- accounting for the roofline discussion ---
    def decode_bytes_per_token(self) -> float:
        """Weights + cache bytes streamed per generated token (model-level)."""
        from ..serve.kv_cache import cache_bytes
        from ..utils.tree import param_bytes
        w = param_bytes(self.model.param_shapes())
        c = cache_bytes(self.model.cache_shape(self.batch_size, self.max_len))
        return w + c / max(1, self.batch_size)


class BatchingSpMVServer:
    """Micro-batching SpMV serving: coalesce concurrent requests into SpMM.

    The operator-level continuation of the token engine above, built on the
    paper's bound: a single SpMV re-streams the whole matrix per call, so
    single-request throughput saturates at BW / balance.  Batching k
    concurrent ``y = A @ x`` requests into one ``plan.spmm(X)`` streams the
    matrix once for all k (``perfmodel.spmm_balance_of``) — the only lever
    that lifts the ceiling.

    Each registered operator gets a compiled plan (``SpMVPlan``, or
    ``DistributedSpMVPlan`` via ``register_distributed`` — both are served
    uniformly) plus an ``OperatorQueue`` whose flush width comes from the
    SpMM roofline (``perfmodel.select_batch_width``) unless overridden.
    Requests enter through ``submit``/``submit_many`` and resolve as
    ``SpMVFuture``s when the batch flushes: width reached, deadline elapsed
    (checked at submission and by ``pump()``), or a consumer forcing
    ``result()``.  Partial batches are zero-padded to the policy width so
    the jitted executor sees one shape.  ``max_pending`` caps each queue;
    beyond it ``submit`` sheds load with ``BackpressureError``.

    The batcher is cooperative and single-threaded; ``clock`` is injectable
    so deadline behavior is testable without sleeping.
    """

    def __init__(self, *, backend: str = "auto", chip=None,
                 am: PM.AccessModel | None = None,
                 max_batch: int | None = None, deadline_s: float = 1e-3,
                 max_pending: int = 256, pad_partial: bool = True,
                 clock=time.monotonic, validate: str = "strict",
                 resilience=None):
        """Args:
            backend: plan backend ("auto" | "xla" | "pallas").
            chip: roofline parameters; defaults to TPU v5e.
            am: access model (byte widths) for the batching policy.
            max_batch: server-wide flush-width override; None lets
                ``perfmodel.select_batch_width`` decide per operator.
            deadline_s: default latency bound for partial batches.
            max_pending: default per-operator queue cap (backpressure).
            pad_partial: zero-pad partial batches to the policy width.
            clock: monotonic time source (injectable for tests).
            validate: request-vector policy ("strict" | "repair" | "off")
                applied at ``submit`` and to registered matrices
                (``core.validate``).  Strict rejects bad shapes and
                NaN/Inf payloads at the offending caller.
            resilience: a ``serve.resilience.ResiliencePolicy`` for the
                flush path (deadlines, retry-with-split, circuit breaker
                + backend degradation).  None uses the defaults; pass
                ``ResiliencePolicy(enabled=False)`` for the legacy
                propagate-and-strand behavior (benchmark mode).
        """
        from ..core.validate import POLICIES
        from ..utils.hw import TPU_V5E
        from .resilience import ResiliencePolicy
        if validate not in POLICIES:
            raise ValueError(f"validate={validate!r}; expected one of {POLICIES}")
        self.backend = backend
        self.chip = chip or TPU_V5E
        self.am = am
        self.max_batch = max_batch
        self.deadline_s = deadline_s
        self.max_pending = max_pending
        self.pad_partial = pad_partial
        self._clock = clock
        self.validate = validate
        self.resilience = resilience if resilience is not None else (
            ResiliencePolicy())
        self._queues: dict[str, OperatorQueue] = {}

    # -- registration -------------------------------------------------------

    def _policy(self, policy_matrix, max_batch, deadline_s,
                max_pending, kernel: str = "xla") -> BatchPolicy:
        # the executed kernel's stream-byte regime (flat vs padded SELL
        # views) feeds the width policy; the label mapping is the plan
        # layer's, shared rather than duplicated
        from ..core.plan import _LABEL_STREAM
        width = max_batch if max_batch is not None else self.max_batch
        if width is None:
            width = PM.select_batch_width(
                policy_matrix, am=self.am, chip=self.chip,
                backend=_LABEL_STREAM.get(kernel, "xla")).width
        return BatchPolicy(
            width=int(width),
            deadline_s=self.deadline_s if deadline_s is None else deadline_s,
            pad_to_width=self.pad_partial,
            max_pending=self.max_pending if max_pending is None else max_pending,
        )

    def _server_config(self, config, plan_kw, *, api: str):
        """Fold kwargs into a ``PlanConfig`` and apply the server's floor:
        the server owns the chip, ``backend="auto"`` defers to the
        server-wide backend, and ``validate=None`` inherits the server's
        validation policy."""
        from ..core.planconfig import coerce_config
        cfg = coerce_config(config, plan_kw, api=api, stacklevel=4)
        return cfg.replace(
            chip=self.chip,
            backend=self.backend if cfg.backend in (None, "auto") else cfg.backend,
            validate=self.validate if cfg.validate is None else cfg.validate)

    def register(self, name: str, matrix, *, max_batch: int | None = None,
                 deadline_s: float | None = None,
                 max_pending: int | None = None,
                 config=None, **plan_kw):
        """Compile ``matrix`` into a plan + batching queue; returns the report.

        Compilation is idempotent (plans are memoized on the container);
        re-registering a name replaces its queue and resets its stats.

        Args:
            name: operator key used by ``submit``/``spmv``/``stats``.
            matrix: any ``core.formats`` container.
            max_batch: flush-width override for this operator.
            deadline_s / max_pending: per-operator policy overrides.
            config: a ``core.planconfig.PlanConfig`` carrying every compile
                option — ``format="auto"`` registers a CSR under the
                perfmodel's chosen storage scheme, ``sigma`` the SELL
                sorting window, ``backend`` a per-operator registry
                override (``"auto"`` = the server-wide setting), and
                ``validate`` overrides the server's matrix-validation
                policy (``None`` inherits it).
            **plan_kw: deprecated bare-kwarg aliases for the config fields
                (one ``DeprecationWarning``, folded into a config).
        """
        from .resilience import degradation_ladder
        cfg = self._server_config(config, plan_kw,
                                  api="BatchingSpMVServer.register")
        plan = SpMVPlan.compile(matrix, cfg)
        # batch-width policy from the container AND kernel the plan actually
        # executes (after any format="auto" conversion / backend selection),
        # not the registered source
        policy = self._policy(plan.matrix, max_batch, deadline_s, max_pending,
                              kernel=plan.report.kernel)

        def rebuild(be, _m=matrix, _cfg=cfg):
            # matrix already checked at register time
            return SpMVPlan.compile(_m, _cfg.replace(backend=be,
                                                     validate="off"))

        self._queues[name] = OperatorQueue(
            plan, policy, self._clock,
            validate=self.validate, resilience=self.resilience,
            rebuild=rebuild,
            ladder=degradation_ladder(plan.report.format, plan.report.kernel,
                                      plan.matrix))
        return plan.report

    def register_distributed(self, name: str, matrix, *, mesh=None,
                             variant: str = "overlap",
                             max_batch: int | None = None,
                             deadline_s: float | None = None,
                             max_pending: int | None = None,
                             config=None, **plan_kw):
        """Mesh-aware registration: compile ``matrix`` into a
        ``DistributedSpMVPlan`` sharded over ``mesh`` (default: all local
        devices).  Batching applies unchanged — ``plan.spmm`` is one
        *distributed* pass, so coalescing also amortizes the collective
        x-shard exchange across the batch, not just the HBM matrix stream.
        ``config.backend`` (``"auto"`` = the server-wide setting) selects
        the registry entry for the inner slab multiplies; bare kwargs
        remain as deprecated aliases.
        """
        from ..core.distributed_plan import _as_csr, compile_distributed_spmv_plan
        from ..core.validate import validate_matrix

        cfg = self._server_config(config, plan_kw,
                                  api="BatchingSpMVServer.register_distributed")
        matrix = validate_matrix(matrix, policy=self.validate)
        plan = compile_distributed_spmv_plan(matrix, mesh, variant=variant,
                                             config=cfg)
        policy = self._policy(_as_csr(matrix), max_batch, deadline_s, max_pending)
        # the inner slab multiplies know exactly two backends (xla and the
        # loop oracles — see ``_resolve_slab_backend``), so the distributed
        # ladder is at most one rung, and none on a TPU (host-only oracles)
        from ..kernels.registry import on_tpu
        ladder = ([] if plan.slab_backend == "loop_reference" or on_tpu()
                  else ["loop_reference"])

        def rebuild(be, _m=matrix, _mesh=mesh, _v=variant, _cfg=cfg):
            return compile_distributed_spmv_plan(_m, _mesh, variant=_v,
                                                 config=_cfg.replace(backend=be))

        self._queues[name] = OperatorQueue(
            plan, policy, self._clock,
            validate=self.validate, resilience=self.resilience,
            rebuild=rebuild, ladder=ladder)
        return plan.report

    # -- batched submission -------------------------------------------------

    def submit(self, name: str, x: jnp.ndarray, *,
               timeout_s: float | None = None) -> SpMVFuture:
        """Enqueue one ``y = A @ x`` request; returns its future.

        Flushes the operator's batch when the policy width is reached or
        its deadline has elapsed; width-1 policies execute synchronously
        (exactly ``plan(x)``).  Raises ``BackpressureError`` at the
        ``max_pending`` cap.  ``timeout_s`` overrides the resilience
        policy's per-request deadline (requests still queued past it are
        shed with ``DeadlineExceeded`` at flush time).
        """
        return self._queues[name].submit(x, timeout_s=timeout_s)

    def submit_many(self, name: str, xs) -> list[SpMVFuture]:
        """Submit a burst of requests in order; returns their futures."""
        return [self.submit(name, x) for x in xs]

    def pump(self) -> int:
        """Flush every operator queue whose deadline has elapsed.

        The cooperative stand-in for a background flusher thread: an
        open-loop driver calls this between arrivals.  Returns the number
        of requests answered.
        """
        return sum(q.flush() for q in self._queues.values()
                   if q.due())

    def flush(self, name: str | None = None) -> int:
        """Force-flush one operator (or all); returns requests answered."""
        if name is not None:
            return self._queues[name].flush()
        return sum(q.flush() for q in self._queues.values())

    def pending(self, name: str) -> int:
        """Queued (not yet executed) request count for one operator."""
        return len(self._queues[name])

    # -- direct (unbatched) paths ------------------------------------------

    def plan(self, name: str) -> SpMVPlan:
        """The compiled plan behind a registered operator."""
        return self._queues[name].plan

    def spmv(self, name: str, x: jnp.ndarray) -> jnp.ndarray:
        """One synchronous query, bypassing the batcher (counted in stats)."""
        self._queues[name].stats.calls += 1
        return self._queues[name].plan(x)

    def spmm(self, name: str, X: jnp.ndarray) -> jnp.ndarray:
        """One caller-assembled batch: X (N, K) -> Y (M, K), counted as K
        queries and one batch (the caller did the coalescing)."""
        self._queues[name].stats.record_batch(int(X.shape[1]))
        return self._queues[name].plan.spmm(X)

    # -- accounting ---------------------------------------------------------

    def stats(self) -> dict:
        """Per-operator serving stats for the roofline discussion.

        Beyond the plan report fields, each entry carries the batching
        counters: ``requests`` (submitted), ``calls`` (queries answered),
        ``batches``, ``mean_batch_width`` (real columns per flush),
        ``padding_ratio`` (zero columns / streamed columns), the
        policy's ``batch_width``/``deadline_s``, and the robustness
        counters — ``shed`` (backpressure rejections), ``retried``
        (batch re-executions), ``degraded`` (backend-ladder steps),
        ``deadline_missed`` (requests shed with ``DeadlineExceeded``),
        ``failed`` (requests resolved with a structured error),
        ``breaker_trips``, and the remaining degrade ``ladder``.
        """
        out = {}
        for name, q in self._queues.items():
            r = q.plan.report
            st = q.stats
            out[name] = {
                "calls": st.calls,
                "requests": st.requests,
                "batches": st.batches,
                "mean_batch_width": st.mean_batch_width,
                "padding_ratio": st.padding_ratio,
                "fast_path_calls": st.fast_path_calls,
                "shed": st.shed,
                "retried": st.retried,
                "degraded": st.degraded,
                "deadline_missed": st.deadline_missed,
                "failed": st.failed,
                "breaker_trips": q.breaker.trips,
                "ladder": tuple(q.ladder),
                "pending": len(q),
                "batch_width": q.policy.width,
                "deadline_s": q.policy.deadline_s,
                "format": r.format,
                "kernel": r.kernel,
                "nnz": r.nnz,
                "predicted_gflops": r.predicted_gflops,
                "predicted_bytes_per_call": r.balance_bytes_per_flop * 2.0 * r.nnz,
            }
            plan = q.plan
            if hasattr(plan, "variant"):  # distributed plans: mesh-level stats
                out[name].update({
                    "variant": plan.variant,
                    "parts": plan.parts,
                    "slab_format": plan.slab_format,
                    "imbalance": plan.imbalance,
                    "local_fraction": plan.local_fraction,
                    "collective_bytes_per_call": plan.traffic["collective"],
                })
        return out


class SparseOperatorServer(BatchingSpMVServer):
    """Back-compat name for the direct-call serving surface.

    Pre-batching code registered operators and called ``spmv``/``spmm``
    synchronously; that surface is unchanged on ``BatchingSpMVServer``, so
    this subclass only keeps the old name importable.  New code should use
    ``BatchingSpMVServer`` and the ``submit`` path.
    """
