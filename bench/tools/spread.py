#!/usr/bin/env python3
"""Spread of each metric over sets of runs written by repeat.py.

    python3 bench/tools/spread.py chiprun_out/train.ckpt.set1.jsonl \
        chiprun_out/train.ckpt.set2.jsonl

For each file and metric: the median and the spread (the distance
between the first and third quartiles of `statistics.quantiles(n=4)`,
as a share of the median), then the wider of the sets' spreads and five
times it, the bound that spread would ask for. Runs that failed or came
out not correct are listed and left out.
"""
from __future__ import annotations

import json
import statistics
import sys


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths: list[str]) -> int:
    per_set = {}
    for path in paths:
        runs = [json.loads(x) for x in open(path) if x.strip()]
        good = [r for r in runs if r.get("result") and r["result"]["correct"]]
        for r in runs:
            if r not in good:
                print(f"{path}: seed {r['seed']} rc {r['rc']} "
                      f"correct {bool(r.get('result'))} left out")
        metrics = {}
        for r in good:
            for k, m in r["result"]["metrics"].items():
                metrics.setdefault(k, []).append(m["value"])
        per_set[path] = metrics
        for k, v in sorted(metrics.items()):
            s = spread(v) if len(v) >= 2 else float("nan")
            print(f"{path}: {k}: n={len(v)} median={statistics.median(v)!r}"
                  f" spread={s:.4f} values={v}")
    names = sorted(set().union(*(m.keys() for m in per_set.values())))
    for k in names:
        s = [spread(m[k]) for m in per_set.values() if len(m.get(k, ())) >= 2]
        if s:
            print(f"{k}: widest spread {max(s):.4f} -> 5x = {5 * max(s):.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
