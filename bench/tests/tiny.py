"""Tiny configurations and cells for running the harness on the CPU."""
from __future__ import annotations

import glob
import os
import tempfile
import time

from bench import harness

TINY_DENSE = {
    "name": "tiny", "family": "dense", "num_hidden_layers": 2,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "qkv_bias": True,
    "tie_word_embeddings": True, "param_dtype": "float32",
    "compute_dtype": "float32",
}


def workload_files(driver: str | None = None) -> list[str]:
    """Names of the workload files under bench/workloads, of one driver
    when given: cells of BENCHMARK.json and those built for a later one."""
    names = sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
        os.path.join(harness.BENCH, "workloads", "*.json")))
    return [n for n in names if driver is None or harness.load_json(
        os.path.join(harness.BENCH, "workloads", f"{n}.json"))["driver"]
        == driver]


def tiny_workload(name: str) -> dict:
    """A workload file with its sizes cut to run on the CPU."""
    spec = harness.load_json(os.path.join(harness.BENCH, "workloads",
                                          f"{name}.json"))
    spec["name"] = name
    spec["train"].update(batch=2, seq=32, save_every_s=0.5)
    return spec


def context(name: str, seed: int = 7, seconds: float = 2.0,
            trace: bool = False, cfg: dict = TINY_DENSE,
            work_dir: str | None = None):
    import jax
    from bench.run import Context
    harness.load_repro()
    return Context(workload=tiny_workload(name), config=dict(cfg),
                   seed=seed, seconds=seconds, trace=trace,
                   devices=jax.devices()[:1], spans=harness.Spans(),
                   compiles=harness.CompileCounter().__enter__(),
                   t_start=time.perf_counter(),
                   work_dir=work_dir or tempfile.mkdtemp())
