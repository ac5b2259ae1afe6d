#!/usr/bin/env python3
"""Host nanoseconds one program span costs, entered and left, with no
profiler running, with the profiler as the benchmark starts it (its
Python call tracer on), and with that tracer off.

    python3 bench/tools/span_cost.py [--n 200000] [--out FILE]

Each case times `n` spans `repro.core.spans.span("cost.probe", step=i)`
in a loop, less the same loop with an empty body, best of three.
Prints one JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness                          # noqa: E402


def _loop_ns(n: int, body) -> float:
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter_ns()
        for i in range(n):
            body(i)
        best = min(best, time.perf_counter_ns() - t)
    return best / n


def per_span_ns(n: int) -> float:
    from repro.core.spans import span

    def spanned(i):
        with span("cost.probe", step=i):
            pass

    def empty(i):
        pass
    return _loop_ns(n, spanned) - _loop_ns(n, empty)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    harness.load_repro()
    import jax
    out = {"device": jax.devices()[0].device_kind, "n": args.n,
           "off_ns": per_span_ns(args.n)}
    for key, level in (("on_ns", None), ("on_no_python_tracer_ns", 0)):
        d = tempfile.mkdtemp()
        opts = None
        if level is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = level
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            out[key] = per_span_ns(args.n)
        finally:
            jax.profiler.stop_trace()
            shutil.rmtree(d, ignore_errors=True)
    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
