"""A later PR adds a configuration, a cell and a per-layer metric as new
files and new entries, and the harness finds them with no edit to a file
that exists."""
import hashlib
import json
import os
import shutil

import pytest

from bench import harness


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture
def tree(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    for sub in ("configs", "workloads", "metrics"):
        shutil.copytree(os.path.join(harness.BENCH, sub), bench / sub)
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
