#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip.

    python3 bench/tools/control.py --workload train.ckpt \
        --seeds 1,2,...,12 --control-seeds 1,2,3

For each seed, the program's numbers as a run compares them; for each
control seed, the same numbers with the reference computed in float8 put
in the program's place (the control). Training needs no window: the
first three steps are the ones compared. Each reading is judged as a run
judges it (`harness.within` against the workload's limits) and carries
its `correct`: the program's has to come out true, the control's and
the fault's false. Under `leaves`, every leaf's norms on both sides.
One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness                      # noqa: E402
from bench.run import Context                  # noqa: E402


def train_readings(ctx, control: bool) -> dict:
    """Program against reference; with `control` also the float8
    reference, and the fault of half the batch left out (planted in the
    reference put in the program's place), against the same reference."""
    drv = harness.driver("train")
    cell = drv.TrainCell(ctx)
    prog = drv.program_first_steps(cell)
    cell.close()
    cfg, spec = ctx.config, ctx.workload
    ref = drv.reference_first_steps(cfg, spec, ctx.seed)
    runs = {"program": prog}
    if control:
        runs["control"] = drv.reference_first_steps(cfg, spec, ctx.seed,
                                                    prec="fp8")
        runs["half_batch"] = drv.reference_first_steps(
            cfg, spec, ctx.seed, rows=spec["train"]["batch"] // 2)
    out = {k: judged(drv.first_step_readings(r, ref), spec["limits"])
           for k, r in runs.items()}
    out["leaves"] = {k: leaf_norms(r, ref) for k, r in runs.items()}
    return out


def leaf_norms(prog: dict, ref: dict) -> dict:
    """Every leaf's norm on both sides, [program, reference], for the
    first gradient and the change, and both sides' losses: which leaf sets a reading, and what
    another statistic over the leaves would read."""
    out = {key: {k: [prog[key][k], ref[key][k]] for k in ref[key]}
           for key in ("grad", "update")}
    out["losses"] = [prog["losses"], ref["losses"]]
    return out


def judged(readings: dict, limits: dict) -> dict:
    """The readings with the verdict a run would give them."""
    checks = [{"name": k, "value": v, "limit": limits[k]}
              for k, v in readings.items()]
    return {**readings, "correct": harness.within(checks)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    spec = harness.workload(args.workload)
    cfg = harness.config(spec["config"])
    harness.load_repro()
    devices = harness.require_chips(spec["chips"])
    harness.enable_compile_cache()
    controls = {int(x) for x in args.control_seeds.split(",") if x}
    os.makedirs(harness.WORK_DIR, exist_ok=True)
    with harness.CompileCounter() as compiles:
        for seed in (int(x) for x in args.seeds.split(",")):
            ctx = Context(workload=spec, config=cfg, seed=seed,
                          seconds=0.0, trace=False,
                          devices=devices, spans=harness.Spans(),
                          compiles=compiles, t_start=time.perf_counter(),
                          work_dir=tempfile.mkdtemp(dir=harness.WORK_DIR))
            out = train_readings(ctx, seed in controls)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
