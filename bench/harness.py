"""What every driver shares: finding files by name, the chip check, seeds,
the compile counter, host spans, peaks and the result line.

Importing this module touches no JAX backend; `require_chips` is the
first call that does.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Callable, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")     # fixed: part of the key
WORK_DIR = os.path.join(ROOT, ".bench_work")     # checkpoints of a run


class BenchError(Exception):
    """A run that cannot produce a result (no chip, missing files)."""


# ----------------------------------------------------------- files by name

def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def workload(name: str) -> dict:
    """The cell's entry in BENCHMARK.json merged with its own file."""
    entry = next((w for w in benchmark()["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    spec = load_json(os.path.join(BENCH, "workloads", f"{name}.json"))
    for key in ("config", "chips"):
        if spec[key] != entry[key]:
            raise BenchError(f"{name}: {key} {spec[key]!r} in its file, "
                             f"{entry[key]!r} in BENCHMARK.json")
    return {**spec, "name": name}


def config(name: str) -> dict:
    return load_json(os.path.join(BENCH, "configs", f"{name}.json"))


def cell_metrics(name: str) -> tuple[list[dict], list[dict]]:
    """(end-to-end, per-layer) metric entries that this cell reports."""
    b = benchmark()

    def mine(m):
        return name in m.get("workloads", [name])
    return ([m for m in b["end_to_end"] if mine(m)],
            [m for m in b["per_layer"] if mine(m)])


def _module(kind: str, name: str):
    """bench/<kind>/<name>.py, loaded from its file."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable:
    """`read(ctx)` of bench/metrics/<name>.py."""
    return _module("metrics", name).read


def driver(name: str):
    return _module("drivers", name)


def arch(cfg: dict):
    """The architecture module bench/arch/<name>.py of a configuration,
    named by its file's "arch" key ("dense" where it has none): the
    program's ModelConfig, the seeded weights, the plain reference and
    the FLOP count. An unknown name is an error, never a default."""
    name = cfg.get("arch", "dense")
    known = sorted(f[:-len(".py")] for f in os.listdir(
        os.path.join(BENCH, "arch")) if f.endswith(".py"))
    if name not in known:
        raise BenchError(f"{cfg.get('name')}: no architecture module "
                         f"{name!r}; known: {known}")
    return _module("arch", name)


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(table)}")
    return table[device_kind]


# -------------------------------------------------------------- the program

def load_repro():
    """Import `repro` from this checkout's src/ and nowhere else."""
    pkg = os.path.join(SRC, "repro")
    if not os.path.isdir(pkg):
        raise BenchError(f"no repro package at {pkg}")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import repro.configs
    where = os.path.dirname(os.path.dirname(
        os.path.abspath(repro.configs.__file__)))
    if where != pkg:
        raise BenchError(f"repro imported from {where}, not {pkg}")


# ------------------------------------------------------------------- chips

def require_chips(chips: int) -> list:
    """The first `chips` TPU devices; anything else is an error, never a
    fall-back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless the environment names one; every program is kept,
    so a second run of a cell compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devices) -> dict:
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max((p for p in peak if p is not None),
                                     default=None)}


# ------------------------------------------------------------------- seeds

def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words from any whole-number seed (2**31 and beyond) and
    a stream number, so weights, data and traffic draw apart."""
    return np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, np.uint32)


def seed_key(seed: int, stream: int):
    import jax
    return jax.random.wrap_key_data(seed_words(seed, stream),
                                    impl="threefry2x32")


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                          int(stream)]))


# ------------------------------------------------------- compiles and spans

class CompileCounter:
    """Counts XLA backend compilations (and their seconds) while armed."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self._on = False

    def _listen(self, name, secs, **_):
        if self._on and name == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False

    @contextlib.contextmanager
    def armed(self):
        n, s = self.count, self.seconds
        self._on = True
        box = {}
        try:
            yield box
        finally:
            self._on = False
            box["count"] = self.count - n
            box["seconds"] = self.seconds - s


class Spans:
    """Host spans written from the benchmark's own files: wall intervals
    by name, and `TraceAnnotation`s named `bench.<name>` while a trace
    runs, so idle gaps on the device can be named by what the host did."""

    PREFIX = "bench."

    def __init__(self):
        self.records: dict[str, list[tuple[float, float]]] = {}
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(self.PREFIX + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.setdefault(name, []).append((t0, t1))

    def wrap(self, obj, attr: str, name: str):
        """Replace `obj.attr` (which must exist) by a spanned call."""
        if not hasattr(obj, attr):
            raise BenchError(f"{type(obj).__name__} has no {attr!r}: the "
                             f"span {name!r} has nothing to wrap")
        inner = getattr(obj, attr)

        def call(*args, **kwargs):
            with self.span(name):
                return inner(*args, **kwargs)

        setattr(obj, attr, call)

    def between(self, name: str, t0: float, t1: float) -> list:
        return [(a, b) for a, b in self.records.get(name, ())
                if a >= t0 and b <= t1]


@contextlib.contextmanager
def device_trace(enabled: bool, spans: Spans, out_dir: str):
    """Profile the block (when enabled) into `out_dir`."""
    if not enabled:
        yield
        return
    import jax
    jax.profiler.start_trace(out_dir)
    spans.tracing = True
    try:
        yield
    finally:
        spans.tracing = False
        jax.profiler.stop_trace()


# ----------------------------------------------------------------- results

def checks_line(checks: list[dict]) -> dict:
    """{name: {"value": v, "limit": l}} in the order compared."""
    return {c["name"]: {"value": c["value"], "limit": c["limit"]}
            for c in checks}


def within(checks: list[dict]) -> bool:
    """Every number inside its limit; a limit not yet set (None) holds
    nothing inside it."""
    return all(c["value"] is not None and c["limit"] is not None
               and math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks)


def emit_result(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: list[dict],
                breakdown: Optional[dict] = None):
    """The compared numbers as the last lines of standard error, and the
    result as the last line of standard output, its `checks` key last."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks_line(checks)
    print(json.dumps(line), flush=True)


def note(**fields):
    """An earlier, informative line of standard output."""
    print(json.dumps({"note": fields}), flush=True)
