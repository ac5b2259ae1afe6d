#!/usr/bin/env python3
"""Run one cell traced on the chip and read the program's spans in it.

    python3 bench/tools/span_probe.py --workload train.ckpt --seed 3 \
        --seconds 45 --out spans-3.json [--slice 0.3]

Prints one JSON line and writes the same, with a recorded slice of the
trace when `--slice` is given, to `--out`:
  e2e, metrics      the run's end-to-end numbers and the six per-layer
                    metrics as `bench/run.py --trace 1` reads them;
  spans             `program_spans.reduce`: idle by the innermost span on
                    the loop's line, `step_gap_ms`, `writer_ms`;
  iteration         device idle inside each `repro.train.iter` (mean,
                    median, p99 in ms) and its seconds by child span, for
                    the iteration that saves, those that overlap a
                    `repro.ckpt.write`, and the quiet rest;
  save, write       each save's and each write's seconds by child span,
                    with the device idle under each child of the save;
  lookup_s          seconds to name every idle gap: the scan of
                    `bench.trace` over the loop line's spans against the
                    bisect of `program_spans`.
The slice starts `--slice-lead` seconds before the window's first save
and holds `--slice` seconds: the device events that start in it, the host
spans that overlap it, and a window span cut to it.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness, program_spans, trace       # noqa: E402
from bench.run import Context                          # noqa: E402

SAVE_PARTS = ("save.copies", "ckpt.save", "ckpt.backpressure",
              "ckpt.snapshot", "ckpt.digest")
WRITE_PARTS = ("ckpt.d2h", "ckpt.digest_fold", "ckpt.shards",
               "ckpt.commit")


def _quantiles_ms(xs: list[float]) -> dict:
    xs = sorted(xs)
    if not xs:
        return {}
    return {"n": len(xs), "mean": 1e3 * statistics.fmean(xs),
            "median": 1e3 * statistics.median(xs),
            "p99": 1e3 * xs[min(len(xs) - 1, int(0.99 * len(xs)))],
            "max": 1e3 * xs[-1]}


def _inside(spans, a, b):
    return [x for x in spans if a <= x[1] and x[1] + x[2] <= b]


def analyse(rec: dict, host: list) -> dict:
    t0, t1 = trace.window(rec)
    loop = program_spans.on_loop(host)
    parts = program_spans.Innermost([x for x in loop
                                     if program_spans.is_program(x[0])])
    idle = program_spans.idle_intervals(rec, t0, t1)
    gaps = sorted(g for gs in idle.values() for g in gs)
    n_dev = max(len(idle), 1)

    def idle_in(a, b) -> dict:
        out: dict[str, float] = {}
        for x, y in gaps:
            if y > a and x < b:
                for k, v in parts.split(max(x, a), min(y, b)).items():
                    out[k] = out.get(k, 0.0) + v / n_dev
        return out

    writes = [x for x in host if x[0] == "ckpt.write" and t0 <= x[1] < t1]
    save_at = [s for n, s, _ in loop if n == "train.save"]
    iters = {"save": [], "writing": [], "quiet": []}
    by_child = {k: {} for k in iters}
    for n, s, d in loop:
        if n != "train.iter" or s < t0 or s + d > t1:
            continue
        if any(s <= t < s + d for t in save_at):
            key = "save"
        elif any(w[1] < s + d and s < w[1] + w[2] for w in writes):
            key = "writing"
        else:
            key = "quiet"
        split = idle_in(s, s + d)
        iters[key].append(sum(split.values()))
        for k, v in split.items():
            by_child[key][k] = by_child[key].get(k, 0.0) + v
    saves = []
    for n, s, d in loop:
        if n == "train.save" and t0 <= s < t1:
            kids = _inside([x for x in loop if x[0] in SAVE_PARTS], s, s + d)
            saves.append({"ms": 1e3 * d,
                          "parts_ms": {k: 1e3 * sum(x[2] for x in kids
                                                    if x[0] == k)
                                       for k in SAVE_PARTS},
                          "idle_ms": {k: 1e3 * v for k, v in
                                      idle_in(s, s + d).items()}})
    out_writes = []
    for n, s, d, line, stats in writes:
        kids = [x for x in host if x[3] == line and x[0] in WRITE_PARTS
                and s <= x[1] and x[1] + x[2] <= s + d]
        d2h = [x for x in kids if x[0] == "ckpt.d2h"]
        out_writes.append({
            "step": stats.get("step"), "ms": 1e3 * d,
            "start_after_window_s": s - t0,
            "parts_ms": {k: 1e3 * sum(x[2] for x in kids if x[0] == k)
                         for k in WRITE_PARTS},
            "d2h_bytes": sum(x[4].get("bytes", 0) for x in d2h),
            "d2h_gb_per_s": (sum(x[4].get("bytes", 0) for x in d2h) / 1e9
                             / sum(x[2] for x in d2h)) if d2h else None})
    digest = [x for x in host if x[0] == "ckpt.digest" and t0 <= x[1] < t1]
    return {
        "iteration": {k: {"idle_ms": _quantiles_ms(v),
                          "idle_by_child_s": by_child[k]}
                      for k, v in iters.items()},
        "save": saves, "write": out_writes,
        "digest_bytes": sum(x[4].get("bytes", 0) for x in digest),
        "digest_kernel_bytes": sum(x[4].get("kernel_bytes", 0)
                                   for x in digest)}


def lookup_seconds(rec: dict, host: list) -> dict:
    """Seconds to name every idle gap of the window by the loop line's
    spans: `bench.trace`'s scan against `program_spans`' bisect."""
    t0, t1 = trace.window(rec)
    loop = program_spans.on_loop(host)
    mids = [(a + b) / 2 for gs in program_spans.idle_intervals(
        rec, t0, t1).values() for a, b in gs]
    a = time.perf_counter()
    scan = [trace._span_at(loop, m) for m in mids]
    b = time.perf_counter()
    names = program_spans.Innermost(loop)
    bis = [names.at(m) for m in mids]
    c = time.perf_counter()
    return {"gaps": len(mids), "spans": len(loop), "scan_s": b - a,
            "bisect_s": c - b, "same": scan == bis}


def cut(rec: dict, host: list, start: float, seconds: float) -> dict:
    end = start + seconds
    return {"devices": {d: {k: [e for e in evs if start <= e[1] < end]
                            for k, evs in v.items()}
                        for d, v in rec["devices"].items()},
            "spans": [[trace.WINDOW_SPAN, start, seconds]]
            + [s for s in rec["spans"] if s[0] != trace.WINDOW_SPAN
               and s[1] < end and s[1] + s[2] > start],
            "host": [[program_spans.WINDOW, start, seconds,
                      program_spans.loop_line(host), {}]]
            + [h for h in host if h[0] != program_spans.WINDOW
               and h[1] < end and h[1] + h[2] > start]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=45)
    ap.add_argument("--slice", type=float, default=0.0)
    ap.add_argument("--slice-lead", type=float, default=0.1)
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
    host = program_spans.record(out["trace_dir"])
    red = trace.reduce(rec)
    view = {"trace": red, "records": out["records"], "workload": spec,
            "config": cfg, "chips": len(devices),
            "peaks": harness.peaks(devices[0].device_kind)}
    _, mine = harness.cell_metrics(spec["name"])
    line = {"probe": args.workload, "seed": args.seed, "e2e": out["e2e"],
            "correct": harness.within(out["checks"]),
            "window_s": red["window_s"], "busy_s": red["busy_s"],
            "bench_idle_by_span": red["idle_by_span"],
            "metrics": {m["name"]: harness.metric_reader(m["name"])(view)
                        for m in mine},
            "records": {k: v for k, v in out["records"].items()},
            "spans": program_spans.reduce(rec, host),
            "lookup_s": lookup_seconds(rec, host),
            **analyse(rec, host)}
    print(json.dumps(line, default=str), flush=True)
    if args.slice:
        saves = [h[1] for h in host if h[0] == "train.save"
                 and h[1] >= trace.window(rec)[0]]
        start = (saves[0] if saves else trace.window(rec)[0]) \
            - args.slice_lead
        line["slice"] = cut(rec, host, start, args.slice)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(line, f, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
