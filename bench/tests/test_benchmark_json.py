"""BENCHMARK.json keeps to the benchmark's contract: its shape, names,
limits, and that every name it holds has the file the harness finds."""
import os
import re

import pytest

from bench import harness

B = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(_dim|_rank)$|^(hidden|intermediate|head|.*latent|"
                   r".*state|.*proj).*_size$|expan|experts_per_tok")
E2E = {m["name"]: m for m in B["end_to_end"]}
CELLS = {w["name"]: w for w in B["workloads"]}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"]
    assert os.path.getsize(os.path.join(harness.ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024
    rs = B["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits in its 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs():
    assert 1 <= len(B["configs"]) <= 24
    used = {w["config"] for w in B["workloads"]}
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        f = harness.config(c["name"])
        assert f["name"] == c["name"] and f["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"] if WIDTH.search(k)]


def test_cells():
    assert 1 <= len(CELLS) <= 24
    assert len({(w["config"], w["traffic"]) for w in B["workloads"]}) \
        == len(CELLS)
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(
        1, len(CELLS) // 2)
    for name, w in CELLS.items():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(name) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        driver = harness.workload(name)["driver"]
        assert NAME.match(driver) and os.path.isfile(os.path.join(
            harness.BENCH, "drivers", f"{driver}.py"))
        harness.arch(harness.config(w["config"]))
        e2e, per_layer = harness.cell_metrics(name)
        names = [m["name"] for m in e2e]
        assert "setup_s" in names and len(names) >= 2 and per_layer


@pytest.mark.parametrize("m", B["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25
    assert all(w in CELLS for w in m.get("workloads", CELLS))


@pytest.mark.parametrize("m", B["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    assert _line(m["layer"]) and m["moves"] in E2E
    for w in m["workloads"]:
        assert w in CELLS
        assert w in E2E[m["moves"]].get("workloads", CELLS)
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"
    assert callable(harness.metric_reader(m["name"]))


def test_names_unique():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in B[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(metrics) == len(set(metrics))
