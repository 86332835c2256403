"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds the chips the cell asks for.  The
cell, its configuration, traffic mix, limits and metrics are found by the
names in ``BENCHMARK.json``.  The run builds the operator from the seed,
compiles the plan and warms every shape the mix uses (set-up), measures for
``--seconds`` (``--trace 0``, the end-to-end metrics) or traces a shorter
window under ``jax.profiler`` (``--trace 1``, the per-layer metrics), then
checks a sample of what the window produced against the plain host
reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` a ``breakdown``, and last the ``checks``, each number beside
its limit.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import os
import sys
import time

_T_SCRIPT = time.perf_counter()
_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if sys.path and os.path.abspath(sys.path[0]) == _HERE:
    sys.path[0] = _ROOT   # import the bench as a package, not its files
elif _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

from bench import common  # noqa: E402

_CLOCK = None
_TAG = ["bench"]


def process_age() -> float:
    """Seconds since this process started (from /proc where it exists,
    else since this script was first executed)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return time.clock_gettime(time.CLOCK_BOOTTIME) - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return time.perf_counter() - _T_SCRIPT


def compile_clock(jax):
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = common.CompileClock(jax)
    return _CLOCK


def enable_cache(jax) -> str:
    path = common.cache_dir()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _say(msg: str) -> None:
    print(f"[{_TAG[0]}] {msg}", file=sys.stderr, flush=True)


def _trace_run(jax, drv, seconds: float, ctx) -> None:
    """Run the runner's window under the profiler (host Python tracer off:
    the bench's annotations and the runtime's events stay) and reduce it."""
    from bench import trace
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.raise_error_on_start_failure = True
    try:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            ctx.window = drv.window(seconds, annotate=True)
            time.sleep(0.01)   # let the last dispatched program finish
        finally:
            jax.profiler.stop_trace()
        found = []
        for d, _, files in os.walk(tdir):
            found += [os.path.join(d, f) for f in files if f.endswith(".xplane.pb")]
        if not found:
            _say(f"trace: the profiler wrote no .xplane.pb under {tdir}")
            return
        path = max(found, key=os.path.getsize)
        s = ctx.trace_summary = trace.load(path)
        w = s.annotation_window(drv.annotation)
        if w is None and s.devices:
            _say(f"trace: no {drv.annotation!r} annotation on the host; the window "
                 "is the span of the device's programs")
            w = (min(m[1] for d in s.devices for m in d.modules),
                 max(m[2] for d in s.devices for m in d.modules))
        ctx.trace_window = w
        _say(f"trace: {os.path.getsize(path)} bytes, {len(s.devices)} device planes "
             f"({', '.join(f'{d.name}: {len(d.modules)} programs, clock offset '
                           f'{d.offset_ns} ns' for d in s.devices)}), "
             f"{len(s.host)} host events, window {w}")
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def breakdown(ctx) -> dict | None:
    s, w = ctx.trace_summary, ctx.trace_window
    if s is None or not s.devices or w is None:
        return None
    dev = s.devices[0]
    fams = sorted(s.families(dev, *w).items(), key=lambda kv: -kv[1][1])[:10]
    gaps = sorted(s.idle_gaps(dev, *w), key=lambda g: g[0] - g[1])[:10]
    return {"device_ops": [[name, t * 1e-9] for name, (_, t) in fams],
            "idle_gaps": [[s.host_activity((a + b) / 2), (b - a) * 1e-9] for a, b in gaps]}


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, env=None,
             t_start: float | None = None) -> dict:
    """Set up, measure and check one cell on ``devices``; returns the result
    object (the caller prints it)."""
    import jax
    _TAG[0] = f"{devices[0].platform} {devices[0].device_kind} x{len(devices)}"
    env = dict(env or {})
    env.setdefault("devices", devices)
    t_start = time.perf_counter() - process_age() if t_start is None else t_start
    clock = compile_clock(jax)
    c0 = clock.snapshot()
    ctx = common.Context(cell, seed, seconds, trace, chips=cell.chips,
                         device_kind=devices[0].device_kind)
    op = common.operator_module(cell).build(cell.config, seed)
    ctx.operator = op
    ctx.timings["build_s"] = op.build_s
    drv = common.runner(cell).Runner(ctx, op, env)
    c1 = clock.snapshot()
    ctx.timings["compile_s"] = c1["seconds"] - c0["seconds"]
    ctx.timings["setup_s"] = time.perf_counter() - t_start
    _say(f"setup: {json.dumps({k: round(v, 6) for k, v in ctx.timings.items()})} "
         f"plan {json.dumps(drv.plan_info)}; compiles {c1['count'] - c0['count']} "
         f"(cache hits {c1['hits'] - c0['hits']}, misses {c1['misses'] - c0['misses']})")
    if trace:
        _trace_run(jax, drv, float(cell.traffic["trace_seconds"]), ctx)
    else:
        ctx.window = drv.window(seconds)
    c2 = clock.snapshot()
    win = ctx.window
    _say(f"window: compiles inside {c2['count'] - c1['count']} "
         f"({c2['seconds'] - c1['seconds']:.6f} s); "
         + json.dumps({k: v for k, v in win.items()
                       if not isinstance(v, (list, dict)) or k in ("counters", "errors")}))
    used = devices[: cell.chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in used)

    metrics, missing, unread = {}, [], []
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = common.metric_reader(cell, m["name"]).read(ctx)
        if v is None:
            (unread if trace else missing).append(m["name"])
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    checks = drv.check(win)
    drv.release()
    verdicts = {}
    for name, value in checks.items():
        limit = float(cell.limits[name])
        verdicts[name] = {"value": float(value), "limit": limit}
    never = int(win.get("never_done", 0))
    correct = (all(v["value"] <= v["limit"] for v in verdicts.values())
               and not win.get("failed") and not never and not missing)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(win.get("attempted", 0)),
              "failed": int(win.get("failed", 0)) + never, "metrics": metrics,
              "device": device}
    if trace:
        from bench.readers import busy_ns
        busy = busy_ns(ctx)
        if busy is not None:
            device["busy_s"] = busy * 1e-9
            device["window_s"] = (ctx.trace_window[1] - ctx.trace_window[0]) * 1e-9
        bd = breakdown(ctx)
        if bd is not None:
            result["breakdown"] = bd
    if missing:
        _say(f"metrics not measured: {', '.join(missing)}")
    if unread:
        _say(f"per-layer metrics the trace gave nothing to read: {', '.join(unread)}")
    result["checks"] = verdicts
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        cell = common.resolve(args.workload)
    except (common.BenchError, OSError, KeyError) as e:
        _say(f"bench: {e}")
        return 2
    src = os.path.join(_ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        _say(f"bench: the program is not in this checkout (no {src}/repro)")
        return 2
    sys.path.insert(1, src)
    import jax
    cache = enable_cache(jax)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        _say(f"JAX found no usable backend: {e}")
        return 1
    if devices[0].platform != "tpu":
        _say(f"no TPU (JAX runs on {devices[0].platform}); the benchmark "
             "only measures on the chip")
        return 1
    if len(devices) < cell.chips:
        _say(f"{cell.name} needs {cell.chips} chips, JAX sees {len(devices)}")
        return 1
    _TAG[0] = f"{devices[0].platform} {devices[0].device_kind} x{len(devices)}"
    _say(f"{cell.name} seed {args.seed}; compile cache {cache}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, v in result["checks"].items():
        _say(f"check {name}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
