"""Benchmark driver: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--full] [--only figX]``
``PYTHONPATH=src python -m benchmarks.run --json [PATH] [--bench-tag PR4]``

Prints ``figure,name,value[,extra...]`` CSV rows.  Default sizes finish in
minutes on CPU; ``--full`` uses out-of-cache sizes matching the paper's
methodology ("array lengths ... such that the problem does not fit in any
cache level").  ``--json [PATH]`` runs the plan + serving + corpus
benchmarks only and writes per-format GFlop/s, plan-vs-naive speedups,
distributed variant timings, the serving throughput-vs-batch-width curve,
and the corpus-wide format sweep as a JSON perf-trajectory artifact; when
PATH is omitted it derives ``BENCH_<tag>.json`` from ``--bench-tag``
(parent directories are created either way).  See docs/BENCHMARKS.md for
the BENCH_PR*.json lineage; ``tools/check_bench.py`` gates CI on the
artifact.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

MODULES = [
    "fig2_basic_ops",
    "fig3_stride_sweep",
    "fig3b_gather_split",
    "fig4_gaussian_strides",
    "fig5_matrix_stats",
    "fig6_formats",
    "fig7_blocksize",
    "fig8_parallel_scaling",
    "fig9_partition_balance",
    "perfmodel_validation",
    "plan_bench",
    "serve_throughput",
    "corpus_sweep",
    "backend_sweep",
    "compression_sweep",
    "matrix_free_sweep",
]

#: current perf-trajectory tag; --json with no PATH writes BENCH_<tag>.json
DEFAULT_BENCH_TAG = "PR10"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--bench-tag", default=DEFAULT_BENCH_TAG,
                    help="perf-trajectory tag; the default --json artifact "
                         f"name is BENCH_<tag>.json (default: {DEFAULT_BENCH_TAG})")
    ap.add_argument("--json", nargs="?", const="", default=None, metavar="PATH",
                    help="write the plan/serving/corpus benchmarks as a JSON "
                         "artifact and exit; PATH defaults to BENCH_<tag>.json")
    args = ap.parse_args(argv)
    from repro.utils import compile_cache
    compile_cache.enable()

    if args.json is not None:
        from benchmarks.backend_sweep import run_json as backend_json
        from benchmarks.backend_sweep import tune_json
        from benchmarks.compression_sweep import run_json as compression_json
        from benchmarks.corpus_sweep import run_json as corpus_json
        from benchmarks.matrix_free_sweep import run_json as matrix_free_json
        from benchmarks.plan_bench import run_json
        from benchmarks.serve_throughput import run_json as serve_json
        out_path = Path(args.json or f"BENCH_{args.bench_tag}.json")
        payload = run_json(full=args.full)
        payload["serving"] = serve_json(full=args.full)
        payload["corpus"] = corpus_json(full=args.full)
        payload["backends"] = backend_json(full=args.full)
        payload["compression"] = compression_json(full=args.full)
        payload["tuning"] = tune_json(full=args.full)
        payload["matrix_free"] = matrix_free_json(full=args.full)
        out_path.parent.mkdir(parents=True, exist_ok=True)
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(f"# wrote {out_path}", file=sys.stderr)
        for fmt, e in payload["formats"].items():
            extra = (f" speedup={e['speedup_plan_vs_naive']:.2f}x"
                     if "speedup_plan_vs_naive" in e else "")
            print(f"# {fmt}: {e['gflops_planned']:.3f} GF/s planned{extra}",
                  file=sys.stderr)
        dist = payload.get("distributed", {})
        for variant, e in dist.get("variants", {}).items():
            print(f"# dist/{variant} (d={dist['devices']}): "
                  f"{e['gflops']:.3f} GF/s slab={e['slab_format']}",
                  file=sys.stderr)
        srv = payload["serving"]
        print(f"# serving: {srv['speedup_at_width8']:.2f}x at width 8 "
              f"(policy width {srv['policy']['selected_width']}, "
              f"direction_match={srv['model_direction_match']})",
              file=sys.stderr)
        cs = payload["corpus"]["summary"]
        print(f"# corpus: {cs['n_matrices']} matrices, "
              f"chosen-format match rate {cs['chosen_match_rate']:.2f}, "
              f"geomean chosen-vs-best slowdown "
              f"{cs['geomean_chosen_slowdown']:.2f}x", file=sys.stderr)
        bs = payload["backends"]["summary"]
        print(f"# backends: {payload['backends']['registered_entries']} "
              f"registry entries, auto-backend match rate "
              f"{bs['auto_match_rate']:.2f} over {bs['n_matrices']} matrices",
              file=sys.stderr)
        comp = payload["compression"]["summary"]
        print(f"# compression: bf16/int8 >= 1.3x on "
              f"{comp['n_compression_wins']}/{comp['n_matrices']} matrices, "
              f"geomean int8 speedup {comp['geomean_int8_speedup']:.2f}x, "
              f"holstein int8 eig_err "
              f"{payload['compression']['holstein']['int8']['eig_err']:.2e}",
              file=sys.stderr)
        ts = payload["tuning"]["summary"]
        print(f"# tuning: geomean chosen-vs-best "
              f"{ts['geomean_chosen_vs_best']:.3f} (model-only "
              f"{ts['geomean_model_vs_best']:.3f}), warm hit rate "
              f"{ts['warm_hit_rate']:.2f} over {ts['n_matrices']} matrices",
              file=sys.stderr)
        ms = payload["matrix_free"]["summary"]
        print(f"# matrix_free: geomean "
              f"{ms['geomean_speedup_vs_materialized']:.2f}x vs materialized "
              f"best over {ms['n_matrices']} matrices (worst "
              f"{ms['worst_speedup_vs_materialized']:.2f}x, parity "
              f"{ms['max_parity_rel_err']:.1e})", file=sys.stderr)
        return 0

    failures = 0
    print("figure,name,value,extra1,extra2,extra3")
    for name in MODULES:
        if args.only and args.only not in name:
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            for r in mod.run(full=args.full):
                print(r)
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED: {type(e).__name__}: {e}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
