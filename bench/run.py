#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

With --trace 0 the result's metrics are the cell's end-to-end metrics;
with --trace 1 the same run is profiled and the metrics are the cell's
per-layer metrics, each read by bench/metrics/<metric>.py. The last line
of standard output is the result; the numbers that decided `correct`
are the last lines of standard error and the result's last key. Without
a TPU, or with fewer chips than the cell asks for, or away from the
program's checkout, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()        # set-up is timed from here

import argparse                       # noqa: E402
import dataclasses                    # noqa: E402
import os                             # noqa: E402
import shutil                         # noqa: E402
import sys                            # noqa: E402
import tempfile                       # noqa: E402
import traceback                      # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness            # noqa: E402


@dataclasses.dataclass
class Context:
    workload: dict
    config: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    spans: harness.Spans
    compiles: harness.CompileCounter
    t_start: float
    work_dir: str


def per_layer(ctx: Context, out: dict) -> tuple[dict, dict, dict]:
    """(metrics, device additions, breakdown) of a traced run."""
    from bench import trace
    red = trace.reduce(trace.record(out["trace_dir"]))
    view = {"trace": red, "records": out["records"],
            "workload": ctx.workload, "config": ctx.config,
            "chips": len(ctx.devices),
            "peaks": harness.peaks(ctx.devices[0].device_kind)}
    _, mine = harness.cell_metrics(ctx.workload["name"])
    metrics, silent = {}, []
    for m in mine:
        v = harness.metric_reader(m["name"])(view)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        elif m["name"].endswith("_roofline"):
            # a kernel taken off the path leaves its roofline silent
            print(f"note: {m['name']} read nothing: its kernel did not "
                  f"run in the window", file=sys.stderr, flush=True)
        else:
            silent.append(m["name"])
    if silent:
        raise harness.BenchError(f"{ctx.workload['name']} lists "
                                 f"{silent}, which read nothing")
    return (metrics, {"busy_s": red["busy_s"], "window_s": red["window_s"]},
            trace.breakdown(red))


def execute(ctx: Context) -> dict:
    """Drive the cell and assemble its result (no chip check here)."""
    out = harness.driver(ctx.workload["driver"]).run(ctx)
    device = out["device"]
    breakdown = None
    if ctx.trace:
        metrics, extra, breakdown = per_layer(ctx, out)
        device.update(extra)
    else:
        e2e, _ = harness.cell_metrics(ctx.workload["name"])
        metrics = {m["name"]: {"value": out["e2e"][m["name"]],
                               "unit": m["unit"]} for m in e2e}
    return {"correct": harness.within(out["checks"])
            and all(m["value"] is not None for m in metrics.values()),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device, "checks": out["checks"],
            "breakdown": breakdown}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    work = None
    try:
        spec = harness.workload(args.workload)
        cfg = harness.config(spec["config"])
        harness.load_repro()
        devices = harness.require_chips(spec["chips"])
        harness.note(compile_cache=harness.enable_compile_cache(),
                     workload=args.workload, seed=args.seed)
        os.makedirs(harness.WORK_DIR, exist_ok=True)
        work = tempfile.mkdtemp(dir=harness.WORK_DIR)
        with harness.CompileCounter() as compiles:
            ctx = Context(workload=spec, config=cfg, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace),
                          devices=devices, spans=harness.Spans(),
                          compiles=compiles, t_start=T_START, work_dir=work)
            res = execute(ctx)
    except Exception:           # no result line for a run that failed
        traceback.print_exc()
        return 1
    finally:
        if work is not None:
            shutil.rmtree(work, ignore_errors=True)
    harness.emit_result(**res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
