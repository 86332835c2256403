"""Repeated personalised PageRank solves, back to back.

Each solve is ``repro.core.eigensolver.pagerank(plan, n, teleport_i,
damping, tol, max_iters, dtype=float32)``: GAP's power iteration, stopped
by the L1 change.  Solve i's teleport vector is drawn on the device from
the seed and i: ``0.5 + u`` per vertex, normalised to sum 1.  The window
runs whole solves until ``seconds`` have passed and reports the elapsed
time over the solves it completed.  The check runs the float64 host
recurrence (``bench/pagerank_reference.py``) from the teleport vectors of
a sample of the window's solves, drawn from the seed, and compares the
scores and the iteration counts.
"""
from __future__ import annotations

import time

import numpy as np

from bench import pagerank_reference
from bench.common import numpy_rng, seed_words
from bench.readers import SPMV_PROBE


class Runner:
    annotation = "solve"   # the host span around the traced work

    def __init__(self, ctx, op, env):
        import jax
        import jax.numpy as jnp
        from repro.core.eigensolver import pagerank

        from bench.placement import compile_plan

        self.ctx, self.op, self.env = ctx, op, env
        self.jax, self.jnp, self._pagerank = jax, jnp, pagerank
        t = ctx.cell.traffic
        self.damping = float(t["damping"])
        self.tol = float(t["tolerance"])
        self.max_iters = int(t["max_iterations"])
        t0 = time.perf_counter()
        self.plan, self.plan_info = env.get("compile_plan", compile_plan)(
            ctx.cell.config, op.matrix, env["devices"])
        ctx.timings["plan_s"] = time.perf_counter() - t0
        n = op.n
        self._key = jax.random.wrap_key_data(
            jnp.asarray(seed_words(ctx.seed, 1), dtype=jnp.uint32))

        def bench_teleport(k, i):   # named apart from the program's jits
            u = 0.5 + jax.random.uniform(jax.random.fold_in(k, i), (n,), jnp.float32)
            return u / jnp.sum(u)
        self._teleport = jax.jit(bench_teleport)
        self.records = []      # (index, scores on the device, iterations, spmv)
        self.failures = []     # (index, error)
        self._next = 0
        # warm-up: every iteration runs the same two programs
        self._solve(2)
        self.records.clear()

    def teleport(self, i: int):
        return self._teleport(self._key, np.uint32(i))

    def _solve(self, max_iters: int | None = None):
        i = self._next
        self._next += 1
        try:
            r = self._pagerank(self.plan, self.op.n, self.teleport(i), damping=self.damping,
                               tol=self.tol, max_iters=max_iters or self.max_iters,
                               dtype=self.jnp.float32)
        except Exception as e:  # noqa: BLE001 - a failed solve is counted, not fatal
            self.failures.append((i, f"{type(e).__name__}: {e}"))
            return
        self.records.append((i, r.scores, int(r.n_iterations), int(r.n_spmv)))

    def window(self, seconds: float, annotate: bool = False) -> dict:
        """Solve until ``seconds`` have passed; returns the window record.
        With ``annotate`` (the traced run) each solve is a ``solve`` span,
        after one bare call of the plan in a ``readers.SPMV_PROBE`` span."""
        if annotate:
            x = self.jax.block_until_ready(self.teleport(0))
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
                "steps": sum(r[2] for r in recs), "spmv_calls": sum(r[3] for r in recs),
                "records": recs}

    def check(self, window: dict) -> dict:
        """Over a sample of the window's solves, against the float64 host
        recurrence from the same teleport vectors: the widest relative L1
        error of the scores, and the widest gap in iteration counts."""
        recs = window["records"]
        if not recs:
            return {"score_l1_err": float("inf"), "iterations_gap": float("inf")}
        k = min(len(recs), int(self.ctx.cell.traffic["check_solves"]))
        rng = numpy_rng(self.ctx.seed, 13)
        pick = [0] + list(rng.choice(np.arange(1, len(recs)), size=k - 1, replace=False)
                          if len(recs) > 1 and k > 1 else [])
        err, gap = 0.0, 0
        for j in pick:
            i, scores, iters = recs[j][:3]
            ref, ref_iters, _ = pagerank_reference.pagerank(
                self.op.host.matvec, np.asarray(self.teleport(i), np.float64),
                self.damping, self.tol, self.max_iters)
            err = max(err, pagerank_reference.score_l1_err(np.asarray(scores), ref))
            gap = max(gap, abs(iters - ref_iters))
        return {"score_l1_err": err, "iterations_gap": float(gap)}

    def release(self):
        self.plan = None
        self.records = []
