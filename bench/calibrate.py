"""Read the numbers that ``correct`` compares, for the program and for its
control, over many seeds in one process, at the cell's own size.

    python3 bench/calibrate.py --workload hh_lanczos --seeds 12 --control-seeds 3

For each program seed the cell is set up as a run sets it up and a short
window (``--seconds``) is checked as a run checks it; for each control seed
the same with the control in the program's place: the plain reference one
precision step down (bfloat16 values and vectors, float32 sums) and the
program's own bfloat16 value path.  Prints one JSON line per reading and a
last line with the lower reading (the largest over the program's seeds) and
the upper reading (the smallest over the controls'), from which the limit in
``bench/limits/<workload>.json`` is set.  The benchmark's own runs never run
this.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if sys.path and os.path.abspath(sys.path[0]) == _HERE:
    sys.path[0] = _ROOT
sys.path.insert(1, os.path.join(_ROOT, "src"))

from bench import common, control, run  # noqa: E402


def reading(cell, seed, seconds, devices, kind, value_dtype=None) -> dict:
    import jax
    op = common.operator_module(cell).build(cell.config, seed)
    ctx = common.Context(cell, seed, seconds, False, chips=cell.chips,
                         device_kind=devices[0].device_kind)
    env = {"devices": devices,
           "compile_plan": control.plan_with(kind, op.host, value_dtype)}
    drv = common.runner(cell).Runner(ctx, op, env)
    win = drv.window(seconds)
    t0 = time.perf_counter()
    checks = drv.check(win)
    check_s = time.perf_counter() - t0
    drv.release()
    del drv
    jax.clear_caches()
    return {"seed": seed, "kind": kind, "value_dtype": value_dtype, "checks": checks,
            "attempted": win.get("attempted"), "failed": win.get("failed"),
            "check_s": check_s, "timings": ctx.timings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--first-seed", type=int, default=2**31 + 101)
    args = ap.parse_args(argv)
    import jax
    run.enable_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 1
    cell = common.resolve(args.workload)
    if len(devices) < cell.chips:
        print(f"calibrate: {cell.name} needs {cell.chips} chips", file=sys.stderr)
        return 1
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds + args.control_seeds)]
    lower, upper = {}, {}
    for i, seed in enumerate(seeds):
        if i < args.seeds:
            runs = [("sound", None)]
        else:
            runs = [("bf16_reference", None), ("sound", "bf16")]
        for kind, vd in runs:
            r = reading(cell, seed, args.seconds, devices, kind, vd)
            print(json.dumps(r), flush=True)
            side = lower if (kind == "sound" and vd is None) else upper
            for name, v in r["checks"].items():
                pick = max if side is lower else min
                side[name] = pick(side.get(name, v), v)
    print(json.dumps({"workload": cell.name, "lower": lower, "upper": upper,
                      "device": devices[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
