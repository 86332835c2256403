"""Cells are found by name, a cell added as new files runs, and the harness
refuses to measure without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402


def _no_tpu_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    return env


def test_every_cell_resolves_by_name():
    spec = common.benchmark(ROOT)
    for w in spec["workloads"]:
        cell = common.resolve(w["name"], ROOT, spec)
        assert cell.config["name"] == w["config"]
        assert cell.limits and cell.traffic["runner"]
        assert common.operator_module(cell).build and common.runner(cell).Runner
        for m in cell.end_to_end + cell.per_layer:
            assert callable(common.metric_reader(cell, m["name"]).read)
    with pytest.raises(common.BenchError):
        common.resolve("no_such_cell", ROOT, spec)


def test_a_cell_added_as_new_files_is_found_and_runs(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    base = json.loads((b / "configs" / "holstein_hubbard_1p2m.json").read_text())
    base.pop("sha256")
    (b / "configs" / "tiny_hh.json").write_text(json.dumps(dict(
        base, name="tiny_hh", source="a 4,000-row surrogate", n=4_000)))
    (b / "traffic" / "lanczos_m8.json").write_text(json.dumps({
        "runner": "lanczos", "steps": 8, "reorthogonalize": False,
        "check_solves": 2, "trace_seconds": 0.2}))
    (b / "limits" / "tiny_lanczos.json").write_text(json.dumps({"ritz_gap": 1e-4}))
    (b / "metrics" / "solves.tiny.py").write_text(
        "def read(ctx):\n    return ctx.window.get('solves')\n")
    spec["configs"].append({"name": "tiny_hh", "source": "a 4,000-row surrogate",
                            "file": "bench/configs/tiny_hh.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "tiny_lanczos", "config": "tiny_hh",
                              "traffic": "lanczos_m8", "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append("tiny_lanczos")
    spec["end_to_end"].append({"name": "solves.tiny", "unit": "solves", "better": "higher",
                               "bound": 0.05, "source": "host_clock",
                               "workloads": ["tiny_lanczos"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = common.resolve("tiny_lanczos", tmp_path)
    assert cell.config["n"] == 4_000 and cell.traffic["steps"] == 8
    assert {m["name"] for m in cell.end_to_end} == {"solve_s", "setup_s", "solves.tiny"}

    sys.path.insert(1, str(ROOT / "src"))
    import jax
    from bench import run
    res = run.run_cell(cell, 5, 0.3, False, jax.devices())
    assert res["correct"] is True
    assert res["metrics"]["solves.tiny"]["value"] >= 1
    assert list(res)[-1] == "checks" and res["checks"]["ritz_gap"]["limit"] == 1e-4


def test_harness_exits_nonzero_without_a_tpu():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "hh_lanczos",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=_no_tpu_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_harness_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "hh_lanczos",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=_no_tpu_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
