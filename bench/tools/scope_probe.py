#!/usr/bin/env python3
"""Run one training cell traced on the chip and sum device time by named
scope.

    python3 bench/tools/scope_probe.py --workload train.moe.ckpt --seed 3 \
        --seconds 45 --out chiprun_out/scopes-train.moe.ckpt.json

The program names parts of a layer with `jax.named_scope` (the MoE
layer's `moe.route`, `moe.permute`, `moe.experts`, `moe.unpermute`).
The profiler's device events name only the HLO instruction, so the
scope of each instruction is read from the op_name metadata of the
train step's compiled HLO (compiled again after the run, from the
cache: the same executable). Sums, over the traced window, the device
seconds of the operations under each scope (the innermost match of
`--scopes`), and of the rest by the last part of their op_name, per
train step; also the cell's per-layer metrics, as a traced run reads
them. Operations nested in a loop's event count as themselves; the
loops' own events are left out of the sums.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness, trace                 # noqa: E402
from bench.run import Context, per_layer        # noqa: E402


def step_op_names(ctx) -> dict:
    """{instruction: op_name} of the cell's compiled train step."""
    drv = harness.driver("train")
    cell = drv.TrainCell(ctx)
    try:
        text = cell.tr._jitted.lower(cell.tr.state, cell.feed.batch(0)) \
            .compile().as_text()
    finally:
        cell.close()
    out = {}
    for line in text.splitlines():
        name = re.match(r"\s*(?:ROOT )?(%\S+) = ", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if name and op:
            out[name.group(1)] = op.group(1)
    return out


def by_scope(ops: dict, op_names: dict, pattern: str) -> dict:
    """{scope: [runs, seconds]} of the reduced trace's operations."""
    rx = re.compile(pattern)
    out: dict = {}
    for text, (runs, secs) in ops.items():
        name = text.split(" = ", 1)[0]
        if name.startswith("%while"):
            continue
        op = op_names.get(name, "")
        found = rx.findall(op)
        key = found[-1] if found else (op.rsplit("/", 1)[-1] or "?")
        agg = out.setdefault(key, [0, 0.0])
        agg[0] += runs
        agg[1] += secs
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=45)
    ap.add_argument("--scopes", default=r"moe\.[a-z]+|flash_[a-z_]+")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    spec = harness.workload(args.workload)
    cfg = harness.config(spec["config"])
    harness.load_repro()
    devices = harness.require_chips(spec["chips"])
    harness.enable_compile_cache()
    os.makedirs(harness.WORK_DIR, exist_ok=True)
    with harness.CompileCounter() as compiles:
        ctx = Context(workload=spec, config=cfg, seed=args.seed,
                      seconds=args.seconds, trace=True, devices=devices,
                      spans=harness.Spans(), compiles=compiles,
                      t_start=time.perf_counter(),
                      work_dir=tempfile.mkdtemp(dir=harness.WORK_DIR))
        out = harness.driver(spec["driver"]).run(ctx)
        try:
            metrics = per_layer(ctx, out)[0]
        except harness.BenchError as e:
            metrics = {"error": str(e)}
        op_names = step_op_names(ctx)
    red = trace.reduce(trace.record(out["trace_dir"]))
    steps, step_s = trace.programs_matching(red, r"train_step")
    n = max(steps, 1)
    scopes = by_scope(red["ops"], op_names, args.scopes)
    res = {"probe": args.workload, "seed": args.seed, "steps": steps,
           "step_ms": 1e3 * step_s / n,
           "per_step_ms": {k: 1e3 * s / n for k, (_, s) in sorted(
               scopes.items(), key=lambda kv: -kv[1][1])},
           "metrics": metrics, "device": out["device"],
           "checks": out["checks"], "idle_by_span": red["idle_by_span"],
           "ops": {k: v for k, v in red["ops"].items()}}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(res, f, default=str)
    top = dict(list(res["per_step_ms"].items())[:16])
    print(json.dumps({"probe": args.workload, "steps": steps,
                      "step_ms": res["step_ms"], "per_step_ms": top,
                      "metrics": metrics, "device": out["device"]},
                     default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
