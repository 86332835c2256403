"""Per-backend kernel sweep + the measured autotuning (``--tune``) pass.

The unified kernel registry (``repro.kernels.registry``) claims that
``backend="auto"`` picks a sensible entry per (format, op) from capability
probes + the roofline ranking.  This module measures that claim twice:

* ``measure()`` — for a small corpus subset, the auto-chosen format's
  SpMV is timed under **every registered backend whose probe passes**
  (XLA formulation, Pallas — interpreter off-TPU — and the loop-reference
  oracle), alongside the backend auto actually selected;
* ``tune()`` — the measured-autotuning tier: for **every** corpus matrix,
  the top-k model-ranked (format, backend) candidates are timed and the
  winners persisted to a ``core.tunedb.TuneDB``, together with a re-fit
  of the perfmodel's ``EXEC_EFFICIENCY`` factors
  (``perfmodel.fit_efficiency_from_db``).  Selection then consults the DB
  first (``SpMVPlan.compile(tuning=...)``); with no DB the cold path is
  bitwise-identical to the model-only ranking.

All timing goes through an injectable ``testing.timing.Timer`` so the
tuning lifecycle is testable without wall-clock noise (``FakeTimer``).

Feeds the ``backends`` and ``tuning`` sections of the BENCH_PR*.json
artifact; ``tuning/summary/geomean_chosen_vs_best`` is the warm-path
chosen-vs-best gap CI gates at <= 1.05 (``check_bench --bound``), and the
CLI (``python -m benchmarks.backend_sweep --tune``) writes the DB plus a
model-vs-measured drift table for ``$GITHUB_STEP_SUMMARY``.
"""
from __future__ import annotations

import argparse
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import corpus
from repro.core import perfmodel as PM
from repro.core import tunedb as TDB
from repro.core.plan import _FMT_NAMES, _convert_cached, resolve_format
from repro.kernels import registry as R
from repro.testing.timing import WallTimer

from .common import host_chip, row

#: small, structurally diverse subset (interpret + loop entries are slow;
#: a full-corpus sweep belongs to corpus_sweep.py, which times formats)
MATRICES = ("holstein_exact", "laplace2d", "powerlaw", "blocksparse")

#: loop_reference on big matrices traces O(chunks) segments; cap the clock
LOOP_NNZ_CAP = 50_000

#: backends the tuning pass never times: both are observability modes with
#: explicit ranking derates — persisting their timings as "winners" would
#: be meaningless (and interpret-mode timings are orders slower).
TUNE_EXCLUDED_BACKENDS = ("loop_reference", "pallas_interpret")


def _time_call(fn, x, iters: int, timer=None) -> float:
    return (timer or WallTimer()).measure(fn, (x,), iters=iters)


def sweep_matrix(name: str, *, iters: int = 10, chip=None) -> dict:
    chip = chip or host_chip()
    spec = corpus.get(name)
    m = corpus.build(name)
    obj = resolve_format(m, "auto", chip=chip)
    fmt = _FMT_NAMES[type(obj)]
    flops = 2.0 * m.nnz
    dtype = np.asarray(getattr(obj, "val", m.val)).dtype
    x = jnp.asarray(np.random.default_rng(0)
                    .standard_normal(m.shape[1]).astype(dtype))

    ctx = R.KernelContext(chip=chip)
    auto_be, costs = R.select_backend(obj, fmt, "spmv", ctx)
    backends = {}
    for entry in R.entries(fmt, "spmv"):
        cap = entry.probe(obj, ctx)
        if not cap.ok:
            backends[entry.backend] = {"skipped": cap.reason}
            continue
        if entry.backend == "loop_reference" and m.nnz > LOOP_NNZ_CAP:
            backends[entry.backend] = {"skipped": f"nnz {m.nnz} > loop cap"}
            continue
        fn = entry.build(obj, ctx).jitted()
        t = _time_call(fn, x, iters)
        backends[entry.backend] = {
            "t_measured_s": t,
            "gflops": flops / t / 1e9,
            "predicted_s": costs.get(entry.backend),
        }
    return {
        "family": spec.family,
        "format": fmt,
        "nnz": m.nnz,
        "auto_backend": auto_be,
        "backends": backends,
    }


def measure(*, iters: int = 10, only=None) -> dict:
    chip = host_chip()
    out = {}
    for name in MATRICES:
        if only and only not in name:
            continue
        out[name] = sweep_matrix(name, iters=iters, chip=chip)
    auto_ok = [e for e in out.values()
               if "gflops" in e["backends"].get(e["auto_backend"], {})]
    # did auto pick the measured-fastest of its survivors?
    matches = []
    for e in auto_ok:
        timed = {b: v["t_measured_s"] for b, v in e["backends"].items()
                 if "t_measured_s" in v and b != "loop_reference"}
        if timed:
            matches.append(min(timed, key=timed.get) == e["auto_backend"])
    return {
        "backend": jax.default_backend(),
        "registered_entries": len(R.entries()),
        "matrices": out,
        "summary": {
            "n_matrices": len(out),
            "auto_match_rate": (sum(matches) / len(matches)) if matches else 1.0,
        },
    }


def run(full: bool = False):
    res = measure(iters=20 if full else 10)
    rows = []
    for name, e in res["matrices"].items():
        for be, v in e["backends"].items():
            if "gflops" in v:
                rows.append(row("backend_sweep", f"{name}/{e['format']}/{be}",
                                v["gflops"],
                                "auto" if be == e["auto_backend"] else ""))
    rows.append(row("backend_sweep", "summary",
                    res["summary"]["auto_match_rate"],
                    res["registered_entries"]))
    return rows


def run_json(full: bool = False) -> dict:
    """The ``backends`` section of the BENCH_PR5.json artifact."""
    return measure(iters=20 if full else 10)


# ---------------------------------------------------------------------------
# the measured autotuning tier (--tune)
# ---------------------------------------------------------------------------


def _convert_kwargs(spec: corpus.MatrixSpec, fmt: str) -> dict:
    kw = {}
    if fmt in ("sell", "hybrid"):
        kw = spec.sell_kwargs()
    elif fmt == "bsr":
        kw = {"block_shape": (8, 128)}
    kw.update(spec.convert_kwargs.get(fmt, {}))   # per-spec overrides win
    return kw


def _tune_variants(spec: corpus.MatrixSpec, m) -> list:
    """``(fmt, convert_kwargs, tag)`` candidates for the measured tier.

    SELL/hybrid fan out over the sigma autotune dimension
    (``perfmodel.sell_sigma_candidates``) when the spec does not pin a
    window — each window is a distinct timed candidate whose
    ``convert_kwargs`` carry the sigma, so the TuneDB's winner records the
    *measured* best window (the signature itself stays chunk-geometry
    independent).  ``tag`` is the human-readable candidate label
    (``sell@s64``) used for timer keys and the report.
    """
    out = []
    for fmt in spec.formats:
        kw = _convert_kwargs(spec, fmt)
        if fmt in ("sell", "hybrid") and kw.get("sigma") is None:
            C = kw.get("C", spec.sell_C)
            for sig in PM.sell_sigma_candidates(m.shape[0], C):
                out.append((fmt, dict(kw, sigma=int(sig)), f"{fmt}@s{sig}"))
        else:
            out.append((fmt, kw, fmt))
    return out


def _geomean(xs) -> float:
    xs = [x for x in xs if x and x > 0 and math.isfinite(x)]
    if not xs:
        return 1.0
    return float(math.exp(sum(math.log(x) for x in xs) / len(xs)))


def _model_times(obj, fmt: str, entry, chip) -> tuple[float, float]:
    """(calibrated model seconds, efficiency-1 model seconds) for an entry.

    The calibrated prediction is the entry's own cost hook (derates and
    all) and feeds the drift table; the efficiency-1 prediction is the
    pure byte-model roofline under the entry's stream regime and feeds
    ``perfmodel.fit_efficiency_from_db``.
    """
    ctx = R.KernelContext(chip=chip)
    stream = ("pallas" if entry.backend in ("pallas", "pallas_interpret")
              else entry.backend)
    am = PM.access_model_for(obj)
    balance = PM.balance_of(obj, am, backend=stream)
    t_model = float(entry.cost(obj, ctx))
    t_eff1 = float(PM.predict_exec(fmt, balance, max(1, obj.nnz), chip=chip,
                                   efficiency={fmt: 1.0}).time_s)
    return t_model, t_eff1


def tune_matrix(name: str, db, *, chip=None, top_k: int = 4,
                iters: int = 10, timer=None) -> dict:
    """Time the top-k model-ranked (format, backend) candidates for one
    corpus matrix and record them in ``db``.

    The cold model's own pick is always in the timed set even when it
    falls outside the top-k, so the chosen-vs-best and model-vs-best
    columns of the summary are honest measurements, never imputations.
    """
    chip = chip or host_chip()
    timer = timer or WallTimer()
    spec = corpus.get(name)
    m = corpus.build(name)
    ctx = R.KernelContext(chip=chip)

    # the cold pick this DB entry will be judged against
    cold = PM.select_format(m, chip=chip, C=spec.sell_C,
                            sigma=spec.sell_sigma, allowed=spec.formats)
    cold_obj = _convert_cached(m, cold.format, dict(cold.convert_kwargs))
    cold_be, _ = R.select_backend(cold_obj, cold.format, "spmv", ctx)

    # enumerate probe-surviving real-backend candidates (SELL/hybrid fan
    # out over the sigma windows), rank by the model
    pool = []
    for fmt, kw, tag in _tune_variants(spec, m):
        try:
            obj = _convert_cached(m, fmt, dict(kw))
        except Exception:  # noqa: BLE001 - unconvertible format: not a candidate
            continue
        for entry in R.entries(fmt, "spmv"):
            if entry.backend in TUNE_EXCLUDED_BACKENDS or not entry.auto:
                continue
            if not entry.probe(obj, ctx).ok:
                continue
            t_model, t_eff1 = _model_times(obj, fmt, entry, chip)
            pool.append({"fmt": fmt, "kw": kw, "tag": tag, "obj": obj,
                         "entry": entry,
                         "t_model_s": t_model, "t_model_eff1_s": t_eff1})
    pool.sort(key=lambda c: c["t_model_s"])
    keep = pool[:top_k]

    def _is_cold(c):
        return (c["fmt"] == cold.format and c["entry"].backend == cold_be
                and c["kw"].get("sigma") == cold.convert_kwargs.get("sigma"))

    if not any(_is_cold(c) for c in keep):
        keep += [c for c in pool[top_k:] if _is_cold(c)]

    dtype = np.asarray(m.val).dtype
    x = jnp.asarray(np.random.default_rng(0)
                    .standard_normal(m.shape[1]).astype(dtype))
    cands, cand_times = [], {}
    for c in keep:
        fn = c["entry"].build(c["obj"], ctx).jitted()
        t = timer.measure(fn, (x,),
                          key=f"{name}/{c['tag']}/{c['entry'].backend}",
                          iters=iters)
        cand_times[f"{c['tag']}/{c['entry'].backend}"] = float(t)
        cands.append(TDB.Candidate(
            format=c["fmt"], backend=c["entry"].backend, t_measured_s=float(t),
            t_model_s=c["t_model_s"], t_model_eff1_s=c["t_model_eff1_s"],
            convert_kwargs=dict(c["kw"])))
    db.record(m, chip=chip, candidates=cands, matrix_name=name)

    # warm pick re-derived through the real selection stack (not assumed)
    warm = PM.select_format(m, chip=chip, C=spec.sell_C,
                            sigma=spec.sell_sigma, allowed=spec.formats,
                            tuning=db)
    warm_obj = _convert_cached(m, warm.format, dict(warm.convert_kwargs))
    warm_be, _ = R.select_backend(warm_obj, warm.format, "spmv",
                                  R.KernelContext(chip=chip, tuning=db))

    if not cands:
        raise RuntimeError(f"no timeable SpMV candidate for {name!r} "
                           f"on {jax.default_backend()}")
    # fastest sigma variant per (format, backend): the DB's warm pick for a
    # format is exactly its measured-argmin candidate, sigma included
    timed = {}
    for c in cands:
        k = (c.format, c.backend)
        timed[k] = min(timed.get(k, c.t_measured_s), c.t_measured_s)
    t_best = min(timed.values())
    # the cold pick is forced into the timed set above; the fallbacks only
    # trigger if auto ever picks a TUNE_EXCLUDED backend (derated oracles)
    t_cold = timed.get((cold.format, cold_be),
                       min((t for (f, _), t in timed.items()
                            if f == cold.format), default=t_best))
    t_warm = timed.get((warm.format, warm_be), t_cold)
    return {
        "family": spec.family,
        "nnz": m.nnz,
        "n_candidates": len(cands),
        "best": min(timed, key=timed.get),
        "model_choice": [cold.format, cold_be],
        "warm_choice": [warm.format, warm_be],
        "warm_source": warm.source,
        "t_best_s": t_best,
        "model_vs_best": t_cold / t_best,
        "chosen_vs_best": t_warm / t_best,
        "tuned_speedup_vs_model": t_cold / t_warm,
        "candidates": cand_times,
    }


def tune(db_path=None, *, db=None, matrices=None, top_k: int = 4,
         iters: int = 10, chip=None, timer=None, save: bool = True) -> dict:
    """The full ``--tune`` pass: measure every corpus matrix, persist the
    winners and the re-fit ``EXEC_EFFICIENCY`` factors, and report the
    warm-vs-cold selection quality the CI bound gates.
    """
    chip = chip or host_chip()
    timer = timer or WallTimer()
    if db is None:
        db = TDB.TuneDB.load(db_path) if db_path is not None else TDB.TuneDB()
    per = {}
    for name in (matrices or corpus.names()):
        per[name] = tune_matrix(name, db, chip=chip, top_k=top_k,
                                iters=iters, timer=timer)
    fam = PM.chip_family(chip)
    db.efficiency[fam] = PM.fit_efficiency_from_db(db, chip=chip)
    if save and db.path is not None:
        db.save()
    return {
        "backend": jax.default_backend(),
        "chip": chip.name,
        "chip_family": fam,
        "db_path": str(db.path) if db.path is not None else None,
        "n_entries": len(db),
        "top_k": top_k,
        "matrices": per,
        "efficiency": db.efficiency[fam],
        "summary": {
            "n_matrices": len(per),
            "geomean_chosen_vs_best": _geomean(
                [e["chosen_vs_best"] for e in per.values()]),
            "geomean_model_vs_best": _geomean(
                [e["model_vs_best"] for e in per.values()]),
            "tuned_speedup_vs_model": _geomean(
                [e["tuned_speedup_vs_model"] for e in per.values()]),
            "warm_hit_rate": (sum(e["warm_source"] == "measured"
                                  for e in per.values()) / len(per)
                              if per else 1.0),
        },
    }


def tune_json(full: bool = False) -> dict:
    """The ``tuning`` section of the BENCH_PR8.json artifact (in-memory DB:
    the committed artifact carries the summary, not the machine's DB)."""
    return tune(iters=20 if full else 10, save=False)


def drift_markdown(db) -> str:
    """The model-vs-measured drift table for ``$GITHUB_STEP_SUMMARY``."""
    lines = [
        "| matrix | format/backend | measured s | model s | model/measured | best |",
        "|---|---|---:|---:|---:|---|",
    ]
    for r in TDB.drift_table(db):
        t_model = f"{r['t_model_s']:.3e}" if r["t_model_s"] else "n/a"
        ratio = (f"{r['ratio_model_vs_measured']:.3f}"
                 if r["ratio_model_vs_measured"] else "n/a")
        star = "*" if r["is_best"] else ""
        lines.append(f"| {r['matrix']} | {r['format']}/{r['backend']} "
                     f"| {r['t_measured_s']:.3e} | {t_model} | {ratio} "
                     f"| {star} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="per-backend sweep / measured autotuning (--tune)")
    ap.add_argument("--tune", action="store_true",
                    help="run the measured autotuning pass over the corpus")
    ap.add_argument("--db", default="tunedb.json",
                    help="tuning-DB path (written by --tune)")
    ap.add_argument("--top-k", type=int, default=4)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--only", default=None,
                    help="substring filter on corpus matrix names")
    ap.add_argument("--markdown", action="store_true",
                    help="print the drift table as GitHub markdown")
    args = ap.parse_args(argv)
    if not args.tune:
        for r in run():
            print(r)
        return 0
    names = [n for n in corpus.names() if not args.only or args.only in n]
    db = TDB.TuneDB.load(args.db)
    res = tune(db=db, matrices=names, top_k=args.top_k, iters=args.iters)
    s = res["summary"]
    print(f"tuned {s['n_matrices']} matrices -> {res['db_path']} "
          f"({res['n_entries']} entries)", file=sys.stderr)
    print(f"geomean chosen-vs-best {s['geomean_chosen_vs_best']:.4f}  "
          f"model-vs-best {s['geomean_model_vs_best']:.4f}  "
          f"tuned speedup vs model {s['tuned_speedup_vs_model']:.4f}",
          file=sys.stderr)
    if args.markdown:
        print("### Tuning drift: model vs measured\n")
        print(drift_markdown(db))
        print(f"\ngeomean chosen-vs-best: "
              f"**{s['geomean_chosen_vs_best']:.4f}**  \n"
              f"re-fit efficiency ({res['chip_family']}): "
              f"`{ {k: round(v, 3) for k, v in res['efficiency'].items()} }`")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
