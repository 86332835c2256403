"""Smoke run of the SpMV stack on a TPU, through its public entry points.

    python chip_smoke.py               # one chip: phases (a) and (b)
    python chip_smoke.py --four-chips  # the 4-chip distributed phase only

Phase (a) is the paper's workload: the Holstein-Hubbard surrogate at the
paper's dimension (N = 1,201,200, ~16.8M non-zeros, f32) compiled with
``SpMVPlan.compile(format="auto")``; SpMV and SpMM are checked against a
float64 host CSR reference, a 60-step Lanczos against the same recurrence
run on the host, and ``BatchingSpMVServer`` answers two full batches.
Phase (b) is a structured operator: the 7-point Laplacian on HPCG's local
104^3 grid, which ``format="auto"`` stores matrix-free and runs through the
compiled Pallas kernel.  ``--four-chips`` builds the phase-(a) operator as
an ``overlap`` distributed plan on a 4-device mesh, checks it against the
single-chip plan and the reference, runs the Lanczos solve on the mesh and
prints each device's memory in use.

Every phase prints its format, kernel, compile seconds (JAX's backend
compile time, persistent-cache reads included) and wall seconds.  The last
line of a passing run is ``{"ok": true, "device": {...}}``.  Without a TPU,
or on any failed check, it exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
N_PAPER = 1_201_200          # the paper's Holstein-Hubbard dimension
GRID = 104                   # HPCG's local grid edge
LANCZOS_STEPS = 60
SPMV_TOL = 1e-4              # max |y - y_ref| / max |y_ref|
EIG_TOL = 1e-3               # |E0 - E0_ref| / |E0_ref|


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


class CompileClock:
    """Sums JAX's backend compile durations (a cache read counts as one)
    and the persistent-cache hits and misses, per phase."""

    def __init__(self, jax):
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


# ---------------------------------------------------------------------------
# host references (numpy float64, independent of the code under test)
# ---------------------------------------------------------------------------


class HostCSR:
    def __init__(self, m):
        import numpy as np
        rp = np.asarray(m.row_ptr, dtype=np.int64)
        self.n = m.shape[0]
        self.rows = np.repeat(np.arange(self.n), np.diff(rp))
        self.col = np.asarray(m.col_idx, dtype=np.int64)
        self.val = np.asarray(m.val, dtype=np.float64)

    def matvec(self, x):
        import numpy as np
        x = np.asarray(x, dtype=np.float64)
        if x.ndim == 2:
            return np.stack([self.matvec(x[:, j]) for j in range(x.shape[1])], 1)
        return np.bincount(self.rows, weights=self.val * x[self.col],
                           minlength=self.n)


def rel_err(y, ref) -> float:
    import numpy as np
    y = np.asarray(y, dtype=np.float64)
    return float(np.abs(y - ref).max() / max(1e-30, np.abs(ref).max()))


def host_lanczos_e0(matvec, v0, steps: int) -> float:
    """The recurrence of ``core.eigensolver.lanczos`` (full, twice-applied
    reorthogonalization) in float64 on the host; returns the lowest Ritz
    value."""
    import numpy as np
    n = v0.shape[0]
    V = np.empty((steps + 1, n))
    V[0] = v0 / np.linalg.norm(v0)
    alphas, betas = [], []
    beta, v_prev = 0.0, np.zeros(n)
    for j in range(steps):
        v = V[j]
        w = matvec(v)
        alpha = float(v @ w)
        w = w - alpha * v - beta * v_prev
        B = V[: j + 1]
        w = w - B.T @ (B @ w)
        w = w - B.T @ (B @ w)
        beta_new = float(np.linalg.norm(w))
        alphas.append(alpha)
        betas.append(beta_new)
        if beta_new < 1e-12 * max(1.0, abs(alpha)):
            break
        v_prev = v
        V[j + 1] = w / beta_new
        beta = beta_new
    b = np.asarray(betas[: len(alphas) - 1])
    T = np.diag(alphas) + np.diag(b, 1) + np.diag(b, -1)
    return float(np.linalg.eigvalsh(T)[0])


def lanczos_v0(jax, jnp, n: int):
    """The start vector ``lanczos(..., seed=0, dtype=float32)`` draws."""
    import numpy as np
    return np.asarray(jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32),
                      dtype=np.float64)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def _plan_fields(report) -> dict:
    return {"format": report.format, "kernel": report.kernel}


def phase_paper(env) -> dict:
    """(a) The paper's Holstein-Hubbard workload, solver and server."""
    jax, jnp, chip = env["jax"], env["jnp"], env["chip"]
    from repro.core.eigensolver import lanczos
    from repro.core.matrices import holstein_hubbard_surrogate
    from repro.core.plan import SpMVPlan
    from repro.core.planconfig import PlanConfig
    from repro.serve.engine import BatchingSpMVServer

    out = {}
    t0 = time.perf_counter()
    m = holstein_hubbard_surrogate(N_PAPER, seed=0)
    out["n"], out["nnz"] = m.shape[0], m.nnz
    out["build_s"] = time.perf_counter() - t0
    ref = HostCSR(m)

    t0 = time.perf_counter()
    plan = SpMVPlan.compile(m, PlanConfig(format="auto", chip=chip))
    out["plan_s"] = time.perf_counter() - t0
    out.update(_plan_fields(plan.report))
    check(plan.report.kernel in ("xla", "pallas"),
          f"phase a runs kernel {plan.report.kernel!r}")

    n = m.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    x = jax.random.normal(keys[0], (n,), jnp.float32)
    X = jax.random.normal(keys[1], (n, 4), jnp.float32)
    out["spmv_err"] = rel_err(jax.block_until_ready(plan(x)), ref.matvec(x))
    out["spmm_err"] = rel_err(jax.block_until_ready(plan.spmm(X)), ref.matvec(X))
    check(out["spmv_err"] <= SPMV_TOL, f"spmv error {out['spmv_err']:.3e}")
    check(out["spmm_err"] <= SPMV_TOL, f"spmm error {out['spmm_err']:.3e}")

    t0 = time.perf_counter()
    e0 = float(lanczos(plan, n, m=LANCZOS_STEPS, dtype=jnp.float32).eigenvalues[0])
    out["lanczos_s"] = time.perf_counter() - t0
    e0_ref = host_lanczos_e0(ref.matvec, lanczos_v0(jax, jnp, n), LANCZOS_STEPS)
    out["e0"], out["e0_ref"] = e0, e0_ref
    out["e0_rel"] = abs(e0 - e0_ref) / abs(e0_ref)
    check(out["e0_rel"] <= EIG_TOL, f"Lanczos E0 off by {out['e0_rel']:.3e}")

    srv = BatchingSpMVServer(chip=chip)
    srv.register("hh", m, config=PlanConfig(format="auto"))
    width = srv.stats()["hh"]["batch_width"]
    xs = list(jax.random.normal(jax.random.PRNGKey(2), (2 * width, n), jnp.float32))
    t0 = time.perf_counter()
    futs = srv.submit_many("hh", xs)
    ys = [f.result() for f in futs]
    jax.block_until_ready(ys)
    out["serve_s"] = time.perf_counter() - t0
    out["served"] = len(ys)
    out["serve_err"] = max(rel_err(y, ref.matvec(xi)) for y, xi in zip(ys, xs))
    st = srv.stats()["hh"]
    out["batch_width"] = width
    for k in ("batches", "degraded", "failed", "shed", "deadline_missed"):
        out[k] = st[k]
    check(out["serve_err"] <= SPMV_TOL, f"served error {out['serve_err']:.3e}")
    check(st["calls"] == 2 * width and st["batches"] >= 2,
          f"served {st['calls']} in {st['batches']} batches")
    check(st["degraded"] == 0 and st["failed"] == 0 and st["shed"] == 0
          and st["deadline_missed"] == 0, f"server stats {st}")
    check(st["kernel"] in ("xla", "pallas"), f"server kernel {st['kernel']!r}")
    return out


def phase_stencil(env) -> dict:
    """(b) HPCG's 104^3 7-point Laplacian: matrix-free on the Pallas kernel."""
    jax, jnp, chip = env["jax"], env["jnp"], env["chip"]
    import numpy as np
    from repro.core.matrices import laplacian_3d
    from repro.core.plan import SpMVPlan
    from repro.core.planconfig import PlanConfig

    out = {}
    t0 = time.perf_counter()
    m = laplacian_3d(GRID, GRID, GRID, dtype=np.float32)
    out["n"], out["nnz"] = m.shape[0], m.nnz
    out["build_s"] = time.perf_counter() - t0
    ref = HostCSR(m)
    t0 = time.perf_counter()
    plan = SpMVPlan.compile(m, PlanConfig(format="auto", chip=chip))
    out["plan_s"] = time.perf_counter() - t0
    out.update(_plan_fields(plan.report))
    check(plan.report.format == "matrix_free" and plan.report.kernel == "pallas",
          f"phase b picked {plan.report.format}/{plan.report.kernel}")
    n = m.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(keys[0], (n,), jnp.float32)
    X = jax.random.normal(keys[1], (n, 4), jnp.float32)
    out["spmv_err"] = rel_err(jax.block_until_ready(plan(x)), ref.matvec(x))
    out["spmm_err"] = rel_err(jax.block_until_ready(plan.spmm(X)), ref.matvec(X))
    check(out["spmv_err"] <= SPMV_TOL, f"spmv error {out['spmv_err']:.3e}")
    check(out["spmm_err"] <= SPMV_TOL, f"spmm error {out['spmm_err']:.3e}")
    return out


def phase_four_chips(env) -> dict:
    """The phase-(a) operator as an overlap plan on a 4-device mesh."""
    jax, jnp, chip = env["jax"], env["jnp"], env["chip"]
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.distributed_plan import compile_distributed_spmv_plan
    from repro.core.eigensolver import lanczos
    from repro.core.matrices import holstein_hubbard_surrogate
    from repro.core.plan import SpMVPlan
    from repro.core.planconfig import PlanConfig

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, found {len(devices)}")
    mesh = Mesh(np.asarray(devices[:4]), ("data",))
    out = {}
    t0 = time.perf_counter()
    m = holstein_hubbard_surrogate(N_PAPER, seed=0)
    out["n"], out["nnz"] = m.shape[0], m.nnz
    out["build_s"] = time.perf_counter() - t0
    ref = HostCSR(m)
    n = m.shape[0]

    t0 = time.perf_counter()
    dplan = compile_distributed_spmv_plan(m, mesh, variant="overlap",
                                          config=PlanConfig(chip=chip))
    out["plan_s"] = time.perf_counter() - t0
    out["format"], out["kernel"] = dplan.report.format, dplan.report.kernel
    single = SpMVPlan.compile(m, PlanConfig(format="auto", chip=chip))
    out["single"] = f"{single.report.format}/{single.report.kernel}"

    x = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32)
    y = jax.block_until_ready(dplan(x))
    y_ref = ref.matvec(x)
    out["spmv_err"] = rel_err(y, y_ref)
    out["vs_single_err"] = rel_err(y, np.asarray(single(x), dtype=np.float64))
    check(out["spmv_err"] <= SPMV_TOL, f"spmv error {out['spmv_err']:.3e}")
    check(out["vs_single_err"] <= SPMV_TOL,
          f"distributed vs single-chip {out['vs_single_err']:.3e}")

    t0 = time.perf_counter()
    e0 = float(lanczos(m, n, m=LANCZOS_STEPS, dtype=jnp.float32,
                       mesh=mesh).eigenvalues[0])
    out["lanczos_s"] = time.perf_counter() - t0
    e0_ref = host_lanczos_e0(ref.matvec, lanczos_v0(jax, jnp, n), LANCZOS_STEPS)
    out["e0"], out["e0_ref"] = e0, e0_ref
    out["e0_rel"] = abs(e0 - e0_ref) / abs(e0_ref)
    check(out["e0_rel"] <= EIG_TOL, f"Lanczos E0 off by {out['e0_rel']:.3e}")

    slabs = dplan.operands[:3]
    total = sum(a.nbytes for a in slabs)
    per_dev = {}
    for d in mesh.devices.flat:
        held = sum(s.data.nbytes for a in slabs for s in a.addressable_shards
                   if s.device == d)
        stats = d.memory_stats() or {}
        per_dev[d.id] = held
        print(f"  device {d.id}: slab_bytes={held} bytes_in_use="
              f"{stats.get('bytes_in_use')}", flush=True)
    out["slab_bytes_total"] = total
    check(all(abs(b - total / 4) <= 0.01 * total for b in per_dev.values()),
          f"slab bytes per device {per_dev} of {total}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip distributed phase")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.utils import compile_cache, hw
    except ImportError:
        print("chip_smoke: the repro package is not next to this script "
              f"(no {os.path.join(HERE, 'src', 'repro')})", file=sys.stderr)
        return 2
    import jax
    import jax.numpy as jnp

    cache = compile_cache.enable()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no usable backend: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX runs on {dev.platform}); "
              "this smoke run only counts on the chip", file=sys.stderr)
        return 1
    chip = hw.chip_for_device(dev)
    print(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)}; "
          f"chip spec {chip.name}; compile cache {cache}", flush=True)

    env = {"jax": jax, "jnp": jnp, "chip": chip}
    phases = ([("4chip", phase_four_chips)] if args.four_chips
              else [("a", phase_paper), ("b", phase_stencil)])
    clock = CompileClock(jax)
    failed = []
    for name, fn in phases:
        c0, h0, m0 = clock.snapshot()
        t0 = time.perf_counter()
        try:
            res, err = fn(env), None
        except Exception as e:  # noqa: BLE001 - every failure is reported
            res, err = {}, f"{type(e).__name__}: {e}"
            traceback.print_exc()
            failed.append(name)
        c1, h1, m1 = clock.snapshot()
        res.update(compile_s=round(c1 - c0, 3), cache_hits=h1 - h0,
                   cache_misses=m1 - m0, wall_s=round(time.perf_counter() - t0, 3),
                   error=err)
        print(f"phase {name}: " + json.dumps(res, default=str), flush=True)
    if failed:
        print(f"chip_smoke: phase(s) {', '.join(failed)} failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                              "kind": dev.device_kind,
                                              "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
