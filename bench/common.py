"""What every part of the benchmark shares: where things are, how a name
is found, seeds, peaks, and the compile clock.

Nothing here imports JAX at module level, so the tests can read it on a
machine without an accelerator.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class BenchError(RuntimeError):
    """A cell, configuration, mix or metric that cannot be run as named."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path}")
    return load_json(path)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: Path, name: str | None = None):
    """Import a file by its path (metric files carry dots in their names)."""
    if not path.is_file():
        raise BenchError(f"no {path}")
    spec = importlib.util.spec_from_file_location(
        name or "bench_dyn_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    workload: dict
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    limits: dict          # bench/limits/<workload>.json
    end_to_end: list      # metric entries this cell reports with --trace 0
    per_layer: list       # metric entries this cell reports with --trace 1
    bench_dir: Path = BENCH_DIR   # where its operator, runner and readers are found

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _applies(metric: dict, cell: str, reported: set | None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # a per-layer metric without a list follows the end-to-end one it moves
    return reported is None or metric.get("moves") in reported


def resolve(name: str, root: Path = ROOT, spec: dict | None = None) -> Cell:
    """Find a cell by its name, and its configuration, mix and metrics by
    the names the cell gives."""
    spec = spec if spec is not None else benchmark(root)
    wl = find(spec["workloads"], name, "workload")
    cfg_entry = find(spec["configs"], wl["config"], "configuration")
    bench_dir = root / "bench"
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(bench_dir / "traffic" / f"{wl['traffic']}.json")
    limits = load_json(bench_dir / "limits" / f"{name}.json")
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, None)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, names)]
    return Cell(wl, config, traffic, limits, e2e, per_layer, bench_dir)


def operator_module(cell: Cell):
    """The operator module the cell's configuration names (``operators/``)."""
    return load_module(cell.bench_dir / "operators" / f"{cell.config['operator']}.py")


def runner(cell: Cell):
    """The traffic runner the cell's mix names (``runners/``)."""
    return load_module(cell.bench_dir / "runners" / f"{cell.traffic['runner']}.py")


def metric_reader(cell: Cell, name: str):
    """The reader of one metric (``metrics/<name>.py``)."""
    return load_module(cell.bench_dir / "metrics" / f"{name}.py")


def seed_words(seed: int, stream: int = 0) -> tuple[int, int]:
    """Two 32-bit words drawn from the whole seed (any size, any sign), so
    that seeds past 2**32 do not fold onto small ones."""
    import numpy as np
    ss = np.random.SeedSequence([seed % (1 << 64), stream])
    w = ss.generate_state(2, dtype=np.uint32)
    return int(w[0]), int(w[1])


def numpy_rng(seed: int, stream: int):
    import numpy as np
    return np.random.default_rng(np.random.SeedSequence([seed % (1 << 64), stream]))


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH_DIR / "peaks.json")["kinds"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]


class CompileClock:
    """Sums JAX's backend compile durations (a persistent-cache read counts
    as one) and the persistent-cache hits and misses."""

    def __init__(self, jax):
        self.seconds, self.count, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"seconds": self.seconds, "count": self.count,
                "hits": self.hits, "misses": self.misses}


@dataclass
class Context:
    """What a run has measured, handed to every metric reader."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    chips: int = 1
    device_kind: str = ""
    timings: dict = field(default_factory=dict)   # build_s, plan_s, compile_s, setup_s
    window: dict = field(default_factory=dict)    # the runner's window record
    trace_summary: object = None                  # bench.trace.Summary or None
    trace_window: tuple | None = None             # (start_ns, end_ns) of the traced work
    operator: object = None                       # Operator

    @property
    def peaks(self) -> dict:
        return peaks(self.device_kind)


def cache_dir(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: a fixed directory inside the
    checkout, whatever the environment names, so that two checkouts share
    no compiled programs and the path (part of the cache key) never moves."""
    return str(root / ".jax_cache")


@dataclass
class Operator:
    """A configuration's operator: the container handed to the program and
    the bench's own host copy that the reference multiplies with."""

    name: str
    n: int
    nnz: int
    dtype: str                 # the value type the configuration states
    stored_values: bool        # False where the values are generated constants
    matrix: object             # the program's container (``repro`` CSR)
    host: object               # bench.reference.HostCSR
    build_s: float             # host seconds in the program's generator
    n_diag: int | None = None  # stored values on the main diagonal where the
                               # configuration states A symmetric, else None


def fingerprint(*arrays) -> str:
    """sha256 over the raw bytes of the arrays, in order."""
    import hashlib

    import numpy as np
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def check_fingerprint(config: dict, *arrays) -> None:
    """Refuse an operator whose generated pattern or values differ from
    the ones the configuration was measured with."""
    want = config.get("sha256")
    if want is not None and fingerprint(*arrays) != want:
        raise BenchError(
            f"configuration {config['name']!r}: the generator's output no longer "
            f"matches the recorded sha256 {want[:12]}...; the deployment changed")
