#!/usr/bin/env python3
"""Run one cell traced on the chip and keep what the trace holds.

    python3 bench/tools/probe.py --workload train.ckpt --seed 3 \
        --seconds 10 --out chiprun_out/probe-train.ckpt.json

Writes the driver's records, the reduced trace (device seconds and runs
per program and per operation, idle seconds by host span) and a short
slice of the recorded trace (`--slice` seconds from the window's start),
the kind of small recorded trace that bench/tests keeps.
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

from bench import harness, trace                 # noqa: E402
from bench.run import Context                    # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--slice", type=float, default=0.5)
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
    rec = trace.record(out["trace_dir"])
    red = trace.reduce(rec)
    t0, _ = trace.window(rec)
    t1 = t0 + args.slice
    cut = {"devices": {d: {k: [e for e in evs if t0 <= e[1] < t1]
                           for k, evs in v.items()}
                       for d, v in rec["devices"].items()},
           "spans": [s for s in rec["spans"]
                     if s[0] == trace.WINDOW_SPAN or t0 <= s[1] < t1]}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"e2e": out["e2e"], "checks": out["checks"],
                   "records": {k: v for k, v in out["records"].items()},
                   "device": out["device"],
                   "window_s": red["window_s"], "busy_s": red["busy_s"],
                   "programs": {k: v[:2] for k, v in
                                red["programs"].items()},
                   "ops_top": sorted(([k, v[0], v[1]] for k, v in
                                      red["ops"].items()),
                                     key=lambda x: -x[2])[:60],
                   "idle_by_span": red["idle_by_span"],
                   "planes": {d: {k: len(v) for k, v in dv.items()}
                              for d, dv in rec["devices"].items()},
                   "n_spans": len(rec["spans"]),
                   "slice": cut}, f, default=str)
    print(json.dumps({"probe": args.workload, "window_s": red["window_s"],
                      "busy_s": red["busy_s"], "e2e": out["e2e"],
                      "checks": out["checks"],
                      "programs": {k: v[:2] for k, v in
                                   red["programs"].items()}},
                     default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
