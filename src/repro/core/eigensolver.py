"""Lanczos eigensolver — the paper's host application.

"Solving those systems often requires multiplication of a sparse matrix with
a vector as the dominant operation ... the fraction spent in the sparse
matrix-vector multiplication may easily constitute over 99 % of total run
time" (Sec. 1).  This module supplies that surrounding algorithm so the
SpMV formats plug into a real solver: plain Lanczos with optional full
reorthogonalization, plus a spectral-extent estimator used by tests, and
PageRank by power iteration with the GAP benchmark suite's semantics.

The SpMV is injected as a closure, so any format / kernel / distribution
strategy (including the shard_map distributed SpMV) drops in unchanged.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import spans

Apply = Callable[[jnp.ndarray], jnp.ndarray]


class LanczosBreakdown(RuntimeError):
    """The Lanczos recurrence produced a non-finite alpha or beta.

    A NaN/Inf in the operator output (a poisoned SpMV, an overflowing
    Hamiltonian entry) contaminates every later iteration — the tridiagonal
    eigenproblem then silently returns NaN Ritz values.  Detection happens
    per iteration, so the error names the first broken step.

    Attributes:
        iteration: 0-based Lanczos step at which the recurrence broke.
        alpha / beta: the offending coefficients (floats, possibly NaN).
    """

    def __init__(self, iteration: int, alpha: float, beta: float):
        super().__init__(
            f"Lanczos recurrence broke down at iteration {iteration}: "
            f"alpha={alpha!r}, beta={beta!r} (non-finite).  The operator "
            "returned NaN/Inf — check the matrix and input vector "
            "(core.validate), or pass on_breakdown='restart' to retry "
            "from a reseeded start vector.")
        self.iteration = iteration
        self.alpha = alpha
        self.beta = beta


def as_apply(op, *, mesh=None, variant: str = "overlap", config=None,
             **plan_kw) -> Apply:
    """Normalize the injected operator: a callable (closure, jitted fn,
    ``SpMVPlan``, or ``DistributedSpMVPlan``) passes through; a bare format
    container is compiled into a plan once, so every Lanczos iteration
    reuses the same cached preprocessing + jitted executor.

    Pass ``mesh`` (and optionally ``variant``) to compile a bare container
    into a comm-overlapped ``DistributedSpMVPlan`` instead — the solver is
    then sharded across the mesh with no other change.  Callables
    (including already-compiled plans) still pass through unchanged.

    ``config`` is a ``core.planconfig.PlanConfig`` forwarded to the
    compile: ``PlanConfig(format="auto")`` lets ``perfmodel.select_format``
    choose the storage scheme from the Hamiltonian's own structure;
    ``value_dtype`` compresses the stored matrix values before planning
    (Lanczos tolerates surprisingly low precision in the matrix apply —
    the recurrence coefficients are still accumulated in f64); ``backend``
    (default ``"auto"``) applies to both the local and the distributed
    compile.  Bare ``format=`` / ``value_dtype=`` / ``backend=`` kwargs are
    deprecated aliases (one ``DeprecationWarning``, folded into a config).
    """
    from .planconfig import coerce_config

    cfg = coerce_config(config, plan_kw, api="eigensolver.as_apply")
    if mesh is not None and not callable(op):
        if cfg.format is not None or cfg.value_dtype is not None:
            raise ValueError(
                "format=/value_dtype= apply to local plans only; distributed compiles "
                "pick their slab packing per partition (see "
                "compile_distributed_spmv_plan's slab_format)")
        from .distributed_plan import compile_distributed_spmv_plan

        return compile_distributed_spmv_plan(op, mesh, variant=variant,
                                             config=cfg)
    if callable(op):
        return op
    from .plan import SpMVPlan

    return SpMVPlan.compile(op, cfg)


@dataclass
class LanczosResult:
    eigenvalues: np.ndarray      # converged Ritz values (ascending)
    alphas: np.ndarray
    betas: np.ndarray
    n_iterations: int
    n_spmv: int
    residuals: np.ndarray        # |beta_m * s_last| per Ritz value


def lanczos(
    apply_A: Apply,
    n: int,
    m: int = 64,
    v0: jnp.ndarray | None = None,
    reorthogonalize: bool = True,
    seed: int = 0,
    dtype=jnp.float64,
    mesh=None,
    config=None,
    on_breakdown: str = "raise",
    max_restarts: int = 2,
    **plan_kw,
) -> LanczosResult:
    """m-step Lanczos on the symmetric operator ``apply_A`` of dimension n.

    Host-level loop (m is small); each iteration performs exactly one SpMV —
    the paper's accounting unit.  With ``reorthogonalize`` the full basis is
    kept and Gram-Schmidt-corrected every step (stable for validation runs).

    ``apply_A`` may be a callable, an ``SpMVPlan``, a
    ``DistributedSpMVPlan``, or a format container (compiled to a plan on
    entry, so every iteration reuses it); with ``mesh`` a CSR container is
    compiled into a distributed plan and the solve shards across devices.
    ``config`` (a ``core.planconfig.PlanConfig``) carries every compile
    option for bare containers — e.g. ``PlanConfig(format="auto")`` picks
    the storage scheme, ``backend`` the kernel-registry entry.  Bare
    ``format=`` / ``value_dtype=`` / ``backend=`` kwargs remain as
    deprecated aliases.

    A non-finite recurrence coefficient (the operator returned NaN/Inf)
    raises :class:`LanczosBreakdown` at the offending iteration instead of
    silently propagating NaN into the Ritz values; ``on_breakdown=
    "restart"`` retries the whole solve from a reseeded start vector up to
    ``max_restarts`` times (a transient fault recovers; a deterministic
    one still raises, carrying the last attempt's breakdown).

    Spans (``utils.spans``): ``lanczos`` around each attempt,
    ``lanczos.step`` around each iteration, and ``lanczos.sync`` around
    the host's blocking reads of that iteration's alpha and beta.
    """
    if on_breakdown not in ("raise", "restart"):
        raise ValueError(f"on_breakdown={on_breakdown!r}; "
                         "expected 'raise' or 'restart'")
    from .planconfig import coerce_config
    cfg = coerce_config(config, plan_kw, api="eigensolver.lanczos")
    apply_A = as_apply(apply_A, mesh=mesh, config=cfg)
    attempts = 1 + (max_restarts if on_breakdown == "restart" else 0)
    n_spmv_prior = 0
    for attempt in range(attempts):
        try:
            with spans.span("lanczos"):
                result = _lanczos_once(
                    apply_A, n, m, v0, reorthogonalize,
                    # reseed each restart (and never reuse a caller v0 that
                    # already broke the recurrence once)
                    seed if attempt == 0 else seed + 7919 * attempt, dtype)
            result.n_spmv += n_spmv_prior
            return result
        except LanczosBreakdown as e:
            n_spmv_prior += e.iteration + 1
            v0 = None
            if attempt == attempts - 1:
                raise
    raise AssertionError("unreachable")  # pragma: no cover


def _lanczos_once(apply_A, n, m, v0, reorthogonalize, seed, dtype) -> LanczosResult:
    if v0 is None:
        v0 = jax.random.normal(jax.random.PRNGKey(seed), (n,), dtype)
    v = v0 / jnp.linalg.norm(v0)
    V = [v]
    alphas, betas = [], []
    beta = 0.0
    v_prev = jnp.zeros_like(v)
    n_spmv = 0
    for j in range(m):
        with spans.span("lanczos.step"):
            w = apply_A(v).astype(dtype)
            n_spmv += 1
            alpha = jnp.vdot(v, w)
            w = w - alpha * v - beta * v_prev
            if reorthogonalize:
                basis = jnp.stack(V)  # (j+1, n)
                w = w - basis.T @ (basis @ w)
                w = w - basis.T @ (basis @ w)  # twice is enough
            beta_new = jnp.linalg.norm(w)
            with spans.span("lanczos.sync"):
                a_j, b_j = float(alpha), float(beta_new)
            if not (np.isfinite(a_j) and np.isfinite(b_j)):
                raise LanczosBreakdown(j, a_j, b_j)
            alphas.append(a_j)
            betas.append(b_j)
            if b_j < 1e-12 * max(1.0, abs(a_j)):
                break
            v_prev = v
            v = w / beta_new
            V.append(v)
            beta = beta_new

    a = np.asarray(alphas)
    b = np.asarray(betas[: len(alphas) - 1])
    T = np.diag(a) + np.diag(b, 1) + np.diag(b, -1)
    evals, evecs = np.linalg.eigh(T)
    resid = np.abs(betas[len(alphas) - 1] * evecs[-1, :]) if len(alphas) else np.zeros(0)
    return LanczosResult(
        eigenvalues=evals,
        alphas=a,
        betas=np.asarray(betas),
        n_iterations=len(alphas),
        n_spmv=n_spmv,
        residuals=resid,
    )


def ground_state_energy(apply_A: Apply, n: int, m: int = 96, **kw) -> float:
    """Smallest Ritz value — the physics observable for the Hamiltonian."""
    return float(lanczos(apply_A, n, m=m, **kw).eigenvalues[0])


def spectral_extent(apply_A: Apply, n: int, m: int = 32, **kw) -> tuple[float, float]:
    r = lanczos(apply_A, n, m=m, **kw)
    return float(r.eigenvalues[0]), float(r.eigenvalues[-1])


def power_iteration(apply_A: Apply, n: int, iters: int = 200, seed: int = 0,
                    dtype=jnp.float64) -> float:
    """|lambda|_max via power iteration — an independent cross-check oracle."""
    apply_A = as_apply(apply_A)
    v = jax.random.normal(jax.random.PRNGKey(seed), (n,), dtype)
    v = v / jnp.linalg.norm(v)

    def body(_, v):
        w = apply_A(v)
        return w / jnp.linalg.norm(w)

    v = jax.lax.fori_loop(0, iters, body, v)
    w = apply_A(v)
    return float(jnp.vdot(v, w))


@dataclass
class PageRankResult:
    scores: jnp.ndarray          # the last iterate, on the device
    n_iterations: int
    n_spmv: int
    l1_changes: np.ndarray       # ||x_k - x_{k-1}||_1 per iteration


@functools.partial(jax.jit, static_argnames="damping")
def _pagerank_update(teleport, x, y, damping):
    x_new = (1.0 - damping) * teleport + damping * y
    return x_new, jnp.sum(jnp.abs(x_new - x))


def pagerank(apply_P: Apply, n: int, teleport, damping: float = 0.85,
             tol: float = 1e-4, max_iters: int = 20,
             dtype=jnp.float32) -> PageRankResult:
    """PageRank by power iteration, as the GAP benchmark suite runs it.

    ``apply_P`` is the column-stochastic transition ``P = A D^-1`` (any
    ``Apply``: a callable, a plan, a bare container or a distributed plan,
    through :func:`as_apply`); ``teleport`` the length-``n`` teleport
    distribution (GAP's is uniform, ``1/n``).  Starting from ``teleport``,
    each iteration is exactly one SpMV ``y = P x`` and one jitted update
    ``x' = (1 - damping) * teleport + damping * y`` that also returns
    ``||x' - x||_1``; the host reads that change once per iteration and
    stops when it falls below ``tol`` (GAP's test), or after
    ``max_iters``.  Vertices without edges leak their mass, as in GAP: no
    dangling redistribution.

    Spans (``utils.spans``): ``pagerank`` around the solve,
    ``pagerank.step`` around each iteration, and ``pagerank.sync`` around
    the host's blocking read of that iteration's L1 change.
    """
    apply_P = as_apply(apply_P)
    t = jnp.asarray(teleport, dtype)
    if t.shape != (n,):
        raise ValueError(f"teleport has shape {t.shape}, expected ({n},)")
    x = t
    changes = []
    with spans.span("pagerank"):
        for _ in range(max_iters):
            with spans.span("pagerank.step"):
                y = apply_P(x).astype(dtype)
                x, err = _pagerank_update(t, x, y, damping=float(damping))
                with spans.span("pagerank.sync"):
                    changes.append(float(err))
            if changes[-1] < tol:
                break
    return PageRankResult(scores=x, n_iterations=len(changes), n_spmv=len(changes),
                          l1_changes=np.asarray(changes))
