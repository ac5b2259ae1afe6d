"""A later PR adds a configuration, a cell and a per-layer metric as new
files and new entries, and the harness finds them with no edit to a file
that exists: also a configuration of another architecture, with its own
architecture module, and a cell of another driver."""
import hashlib
import json
import os
import shutil

import jax
import pytest

from bench import harness


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture
def tree(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    for sub in ("arch", "configs", "drivers", "workloads", "metrics"):
        shutil.copytree(os.path.join(harness.BENCH, sub), bench / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.BENCH, "peaks.json"), bench)
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "BENCH", str(bench))
    return tmp_path


def test_new_files_are_found_by_name(tree):
    before = _digests(tree / "bench")
    cfg = dict(harness.config("qwen2-0.5b-l4"), name="qwen2-0.5b-wide")
    (tree / "bench/configs/qwen2-0.5b-wide.json").write_text(json.dumps(cfg))
    spec = dict(harness.load_json(str(tree / "bench/workloads/train.ckpt.json")),
                config="qwen2-0.5b-wide")
    (tree / "bench/workloads/train.wide.json").write_text(json.dumps(spec))
    (tree / "bench/metrics/steps_run.train.py").write_text(
        "def read(view):\n    return view['records']['steps_run']\n")
    b = json.loads((tree / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "qwen2-0.5b-wide", "source": "x",
                         "file": "bench/configs/qwen2-0.5b-wide.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "train.wide", "config": "qwen2-0.5b-wide",
                           "traffic": "train.wide", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "steps_run.train", "unit": "steps",
                           "better": "higher", "source": "program_counter",
                           "layer": "model step",
                           "moves": "train_tokens_per_s",
                           "workloads": ["train.wide"]})
    b["end_to_end"][0]["workloads"].append("train.wide")
    (tree / "BENCHMARK.json").write_text(json.dumps(b))

    w = harness.workload("train.wide")
    assert w["config"] == "qwen2-0.5b-wide" and w["driver"] == "train"
    assert harness.config(w["config"])["name"] == "qwen2-0.5b-wide"
    e2e, per_layer = harness.cell_metrics("train.wide")
    assert [m["name"] for m in e2e] == ["train_tokens_per_s", "setup_s"]
    assert [m["name"] for m in per_layer] == ["steps_run.train"]
    read = harness.metric_reader("steps_run.train")
    assert read({"records": {"steps_run": 12}}) == 12
    # the cells that were there report what they did before
    assert "steps_run.train" not in [
        m["name"] for m in harness.cell_metrics("train.ckpt")[1]]
    after = _digests(tree / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_a_cell_must_agree_with_its_entry(tree):
    b = json.loads((tree / "BENCHMARK.json").read_text())
    b["workloads"][0]["chips"] = 4
    (tree / "BENCHMARK.json").write_text(json.dumps(b))
    with pytest.raises(harness.BenchError):
        harness.workload(b["workloads"][0]["name"])
    with pytest.raises(harness.BenchError):
        harness.workload("no.such.cell")


ARCH = """
from repro.models.config import ModelConfig


def model_config(cfg):
    return ModelConfig(name=cfg["name"], family="moe", n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, d_ff=16,
                       vocab_size=cfg["vocab_size"],
                       n_experts=cfg["num_experts"],
                       experts_per_token=cfg["num_experts_per_tok"])
"""

DRIVER = """
def run(ctx):
    return {"e2e": {}, "records": {}, "checks": [], "attempted": 0,
            "failed": 0, "device": {}, "trace_dir": None}
"""


def test_another_architecture_and_driver_are_found_by_name(tree):
    before = _digests(tree / "bench")
    (tree / "bench/arch/tiny_moe.py").write_text(ARCH)
    (tree / "bench/drivers/probe_serve.py").write_text(DRIVER)
    cfg = {"name": "tiny-moe-l2", "arch": "tiny_moe", "family": "moe",
           "vocab_size": 64, "num_experts": 4, "num_experts_per_tok": 2,
           "reduced": []}
    (tree / "bench/configs/tiny-moe-l2.json").write_text(json.dumps(cfg))
    (tree / "bench/workloads/serve.tiny.json").write_text(json.dumps(
        {"config": "tiny-moe-l2", "driver": "probe_serve", "chips": 1}))
    b = json.loads((tree / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-moe-l2", "source": "x",
                         "file": "bench/configs/tiny-moe-l2.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "serve.tiny", "config": "tiny-moe-l2",
                           "traffic": "serve.tiny", "chips": 1, "why": "x"})
    (tree / "BENCHMARK.json").write_text(json.dumps(b))

    w = harness.workload("serve.tiny")
    assert callable(harness.driver(w["driver"]).run)
    found = harness.config(w["config"])
    assert found == cfg
    harness.load_repro()
    from repro.models.model import Model
    mc = harness.arch(found).model_config(found)
    assert (mc.family, mc.n_experts, mc.experts_per_token) == ("moe", 4, 2)
    experts = {jax.tree_util.keystr(p): x.shape for p, x in
               jax.tree_util.tree_flatten_with_path(
                   Model(mc).abstract_params())[0] if "moe" in
               jax.tree_util.keystr(p)}
    moe = "['stack']['layers']['moe']"
    assert experts == {moe + "['router']": (2, 32, 4),
                       moe + "['wi_gate']": (2, 4, 32, 16),
                       moe + "['wi_up']": (2, 4, 32, 16),
                       moe + "['wo']": (2, 4, 16, 32)}
    # the configuration that was there keeps its dense module
    assert harness.arch(harness.config("qwen2-0.5b-l4")).__name__ \
        == "bench_arch_dense"
    after = _digests(tree / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_an_architecture_with_no_module_is_an_error(tree):
    cfg = dict(harness.config("qwen2-0.5b-l4"), arch="no_such_arch")
    with pytest.raises(harness.BenchError, match="no_such_arch.*dense"):
        harness.arch(cfg)
