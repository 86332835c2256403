"""Repeated Lanczos solves, back to back: the paper's use case.

Each solve is ``repro.core.eigensolver.lanczos(plan, n, m=steps,
v0=v0_i, reorthogonalize=..., dtype=float32)`` with its own start vector,
drawn on the device from the seed and the solve's index.  The window runs
whole solves until ``seconds`` have passed and reports the elapsed time
over the solves it completed.  The check runs the float64 host recurrence
from the start vectors of a sample of the window's solves, drawn from the
seed, and compares the extreme Ritz values.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference
from bench.common import numpy_rng, seed_words
from bench.readers import SPMV_PROBE


class Runner:
    annotation = "solve"   # the host span around the traced work

    def __init__(self, ctx, op, env):
        import jax
        import jax.numpy as jnp
        from repro.core.eigensolver import lanczos

        from bench.placement import compile_plan

        self.ctx, self.op, self.env = ctx, op, env
        self.jax, self.jnp, self._lanczos = jax, jnp, lanczos
        t = ctx.cell.traffic
        self.steps = int(t["steps"])
        self.reorth = bool(t["reorthogonalize"])
        t0 = time.perf_counter()
        self.plan, self.plan_info = env.get("compile_plan", compile_plan)(
            ctx.cell.config, op.matrix, env["devices"])
        ctx.timings["plan_s"] = time.perf_counter() - t0
        n = op.n
        key = jax.random.wrap_key_data(
            jnp.asarray(seed_words(ctx.seed, 1), dtype=jnp.uint32))
        self._key = key

        def bench_start_vector(k, i):   # named apart from the program's jits
            return jax.random.normal(jax.random.fold_in(k, i), (n,), jnp.float32)
        self._v0 = jax.jit(bench_start_vector)
        self.records = []      # (index, theta_min, theta_max, iterations, spmv)
        self.failures = []     # (index, error)
        self._next = 0
        # warm-up: every step after the second runs the programs of the
        # second, so a three-step solve compiles all that the window uses
        self._solve(min(self.steps, 3))
        self.records.clear()

    def v0(self, i: int):
        return self._v0(self._key, np.uint32(i))

    def _solve(self, steps: int | None = None):
        i = self._next
        self._next += 1
        try:
            r = self._lanczos(self.plan, self.op.n, m=steps or self.steps, v0=self.v0(i),
                              reorthogonalize=self.reorth, dtype=self.jnp.float32)
        except Exception as e:  # noqa: BLE001 - a failed solve is counted, not fatal
            self.failures.append((i, f"{type(e).__name__}: {e}"))
            return
        ev = np.asarray(r.eigenvalues, np.float64)
        self.records.append((i, float(ev[0]), float(ev[-1]), int(r.n_iterations),
                             int(r.n_spmv)))

    def window(self, seconds: float, annotate: bool = False) -> dict:
        """Solve until ``seconds`` have passed; returns the window record.
        With ``annotate`` (the traced run) each solve is a ``solve`` span,
        after one bare call of the plan in a ``readers.SPMV_PROBE`` span."""
        if annotate:
            x = self.jax.block_until_ready(self.v0(0))
            with self.jax.profiler.TraceAnnotation(SPMV_PROBE):
                self.jax.block_until_ready(self.plan(x))
        first = len(self.records)
        nfail = len(self.failures)
        t0 = time.perf_counter()
        while True:
            if annotate:
                with self.jax.profiler.TraceAnnotation("solve"):
                    self._solve()
            else:
                self._solve()
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        recs = self.records[first:]
        return {"elapsed_s": elapsed, "solves": len(recs),
                "attempted": len(recs) + len(self.failures) - nfail,
                "failed": len(self.failures) - nfail,
                "steps": sum(r[3] for r in recs), "spmv_calls": sum(r[4] for r in recs),
                "records": recs}

    def check(self, window: dict) -> dict:
        """The widest Ritz-value gap over a sample of the window's solves,
        against the float64 host recurrence from the same start vectors."""
        recs = window["records"]
        if not recs:
            return {"ritz_gap": float("inf")}
        k = min(len(recs), int(self.ctx.cell.traffic["check_solves"]))
        rng = numpy_rng(self.ctx.seed, 13)
        pick = [0] + list(rng.choice(np.arange(1, len(recs)), size=k - 1, replace=False)
                          if len(recs) > 1 and k > 1 else [])
        gap = 0.0
        for j in pick:
            i, tmin, tmax = recs[j][:3]
            v0 = np.asarray(self.v0(i), np.float64)
            ref = reference.lanczos(self.op.host.matvec, v0, self.steps,
                                    reorthogonalize=self.reorth)
            gap = max(gap, reference.ritz_gap([tmin, tmax], ref))
        return {"ritz_gap": gap}

    def release(self):
        self.plan = None
