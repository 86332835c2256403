"""Record the small profiler trace that the trace-reduction tests read.

    python bench/record_fixture.py --out DIR

Runs a few small SpMV plans, a short Lanczos solve and one batched flush
on the chip under ``jax.profiler``, copies the ``.xplane.pb`` to
``DIR/trace_1chip.xplane.pb`` and prints every plane and line of the trace
with a few event names, so that the reduction in ``bench/trace.py`` can be
checked against what the device really reports.  Exits non-zero without a
TPU.
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True, help="directory for trace_1chip.xplane.pb")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("record_fixture: no TPU", file=sys.stderr)
        return 1
    from repro.core.eigensolver import lanczos
    from repro.core.matrices import holstein_hubbard_surrogate, laplacian_3d
    from repro.core.plan import SpMVPlan
    from repro.core.planconfig import PlanConfig
    from repro.serve.engine import BatchingSpMVServer
    from repro.utils.hw import chip_for_device
    chip = chip_for_device(jax.devices()[0])

    hh = holstein_hubbard_surrogate(20_000, seed=0)
    lap = laplacian_3d(16, 16, 32, dtype=np.float32)
    p_hh = SpMVPlan.compile(hh, PlanConfig(format="auto", chip=chip))
    p_lap = SpMVPlan.compile(lap, PlanConfig(format="auto", chip=chip))
    print("picks:", p_hh.report.format, p_hh.report.kernel, "|",
          p_lap.report.format, p_lap.report.kernel, flush=True)
    srv = BatchingSpMVServer(chip=chip, max_batch=4)
    srv.register("hh", hh, config=PlanConfig(format="auto"))
    xs = list(jax.random.normal(jax.random.PRNGKey(1), (3, hh.shape[0]), jnp.float32))
    v0 = jax.random.normal(jax.random.PRNGKey(0), (hh.shape[0],), jnp.float32)
    v1 = jax.random.normal(jax.random.PRNGKey(0), (lap.shape[0],), jnp.float32)

    def work():
        with jax.profiler.TraceAnnotation("solve", idx=0):
            lanczos(p_hh, hh.shape[0], m=4, v0=v0, reorthogonalize=False,
                    dtype=jnp.float32)
        with jax.profiler.TraceAnnotation("solve", idx=1):
            lanczos(p_lap, lap.shape[0], m=4, v0=v1, reorthogonalize=False,
                    dtype=jnp.float32)
        futs = srv.submit_many("hh", xs)
        srv.flush()
        jax.block_until_ready([f.result() for f in futs])

    work()  # compile outside the trace
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    work()
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))[-1]
    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, "trace_1chip.xplane.pb")
    shutil.copy(src, dst)
    print(f"trace {dst}: {os.path.getsize(dst)} bytes")
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(dst)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:6]:
                stats = {}
                try:
                    stats = {k: v for k, v in e.stats}
                except Exception as exc:  # noqa: BLE001 - a probe prints what it can
                    stats = {"?": repr(exc)}
                print(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns} "
                      f"stats={str(stats)[:300]}")
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
