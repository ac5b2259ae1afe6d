"""Every workload file, cut to a tiny size, runs end to end on the CPU
through the harness below its chip check: correct, every end-to-end
metric its cell reports, and nothing compiled inside the window."""
import math

import pytest

from bench import harness
from bench.run import execute
from bench.tests.tiny import context, workload_files


@pytest.mark.parametrize("name", workload_files())
def test_cell_runs_correct(name, tmp_path):
    ctx = context(name, seed=2**31 + 11, seconds=2.0,
                  work_dir=str(tmp_path))
    res = execute(ctx)
    assert res["correct"], res["checks"]
    if name in [w["name"] for w in harness.benchmark()["workloads"]]:
        e2e, _ = harness.cell_metrics(name)
        assert sorted(res["metrics"]) == sorted(m["name"] for m in e2e)
    for m in res["metrics"].values():
        assert m["value"] is not None and math.isfinite(m["value"])
        assert m["value"] > 0
    assert res["attempted"] > 0
    # the compared numbers come last, each with its limit
    assert all({"name", "value", "limit"} <= set(c) for c in res["checks"])



def test_a_listed_metric_that_reads_nothing_fails_the_run(monkeypatch):
    """A per-layer metric that lists a cell has to read something there;
    only a kernel's roofline may fall silent (its kernel left the path)."""
    from types import SimpleNamespace

    from bench import run, trace
    monkeypatch.setattr(trace, "record", lambda d: {})
    monkeypatch.setattr(trace, "reduce",
                        lambda r: {"busy_s": 1.0, "window_s": 2.0})
    monkeypatch.setattr(trace, "breakdown", lambda r: {})
    name = harness.benchmark()["workloads"][0]["name"]
    _, mine = harness.cell_metrics(name)
    ctx = SimpleNamespace(workload={"name": name}, config={},
                          devices=[SimpleNamespace(
                              device_kind="TPU v5 lite")])
    out = {"trace_dir": "unused", "records": {}}

    def readers(silent):
        return lambda metric: (lambda view: None if silent(metric)
                               else 1.0)
    monkeypatch.setattr(harness, "metric_reader", readers(
        lambda m: m == mine[0]["name"]))
    with pytest.raises(harness.BenchError):
        run.per_layer(ctx, out)
    monkeypatch.setattr(harness, "metric_reader", readers(
        lambda m: m.endswith("_roofline")))
    metrics, device, _ = run.per_layer(ctx, out)
    assert sorted(metrics) == sorted(m["name"] for m in mine
                                     if not m["name"].endswith("_roofline"))
    assert device == {"busy_s": 1.0, "window_s": 2.0}
