#!/usr/bin/env python3
"""Run one cell several times, one process per run, as the check does.

    python3 bench/tools/repeat.py --workload train.ckpt --seeds 11,12,13 \
        --seconds 30 --trace 0 --out chiprun_out/train.ckpt.jsonl

Each run's result line (or its failure, with the end of its standard
error) is appended to --out as one JSON line and echoed. This process
never imports JAX, so each child gets the chip to itself.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

RUN = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "run.py")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--timeout", type=float, default=1200)
    args = ap.parse_args(argv)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    bad = 0
    for seed in args.seeds.split(","):
        t0 = time.monotonic()
        p = subprocess.run(
            [sys.executable, RUN, "--workload", args.workload, "--seed",
             seed, "--seconds", str(args.seconds), "--trace",
             str(args.trace)], capture_output=True, text=True,
            timeout=args.timeout)
        lines = p.stdout.strip().splitlines()
        rec = {"workload": args.workload, "seed": int(seed),
               "trace": args.trace, "rc": p.returncode,
               "wall_s": time.monotonic() - t0,
               "notes": [json.loads(x)["note"] for x in lines[:-1]
                         if x.startswith('{"note"')]}
        try:
            rec["result"] = json.loads(lines[-1]) if p.returncode == 0 \
                else None
        except (IndexError, json.JSONDecodeError):
            rec["result"] = None
        if rec["result"] is None:
            rec["stderr"] = p.stderr[-6000:]
            bad += 1
        line = json.dumps(rec)
        print(line, flush=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
