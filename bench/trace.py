"""Reduce a profiler trace to device busy time, per-program device time
and idle gaps named by the benchmark's host spans.

`record(dir)` reads the newest `.xplane.pb` under `dir` into plain lists:
for each device plane the events of its "XLA Modules" line (one per
program run) and "XLA Ops" line (one per operation), and the host events
named `bench.<span>` that the benchmark's own files wrote. `reduce` works
on those lists alone, so it is tested on a small recorded trace.

All times are seconds on the trace's own clock.
"""
from __future__ import annotations

import glob
import json
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "window"


def program_name(name: str) -> str:
    """A program's name without the run id the profiler appends."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(text: str) -> str:
    """An operation's name and result shape from its HLO text, e.g.
    `%fusion.12 = bf16[8,1024]`; operations of a loop body nest inside the
    loop's own event."""
    head, _, rest = text.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head} = {shape}" if rest else head


def record(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            devices[plane.name] = {
                key: [(name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9)
                      for e in lines[line].events]
                for key, line, name in (
                    ("modules", "XLA Modules", program_name),
                    ("ops", "XLA Ops", op_name)) if line in lines}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      e.start_ns * 1e-9,
                                      e.duration_ns * 1e-9))
    return {"devices": devices, "spans": spans}


def save(rec: dict, path: str):
    with open(path, "w") as f:
        json.dump(rec, f)


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, t0, t1):
    return [(n, max(s, t0), min(s + d, t1)) for n, s, d in events
            if s + d > t0 and s < t1]


def window(rec: dict) -> tuple[float, float]:
    """The measured window: the host span `bench.window`."""
    w = [(s, s + d) for n, s, d in rec["spans"] if n == WINDOW_SPAN]
    if len(w) != 1:
        raise ValueError(f"{len(w)} window spans in the trace")
    return w[0]


def _span_at(spans, t):
    """Innermost host span (other than the window) covering time t."""
    best = None
    for n, s, d in spans:
        if n != WINDOW_SPAN and s <= t < s + d \
                and (best is None or d < best[1]):
            best = (n, d)
    return best[0] if best else "other"


def reduce(rec: dict) -> dict:
    """Busy and idle time inside the window, averaged over the devices;
    device seconds and runs per program and per operation, summed over
    the devices; idle seconds by the host span they fall in."""
    t0, t1 = window(rec)
    win = t1 - t0
    busy, gaps = [], {}
    programs: dict[str, list] = {}
    ops: dict[str, list] = {}
    for dev in rec["devices"].values():
        spans_ops = _clip(dev["ops"], t0, t1)
        merged = _union((s, e) for _, s, e in spans_ops)
        busy.append(sum(e - s for s, e in merged))
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                k = _span_at(rec["spans"], (a + b) / 2)
                gaps.setdefault(k, []).append(b - a)
        for name, s, e in spans_ops:
            agg = ops.setdefault(name, [0, 0.0])
            agg[0] += 1
            agg[1] += e - s
        for name, s, e in _clip(dev.get("modules", []), t0, t1):
            agg = programs.setdefault(name, [0, 0.0, []])
            agg[0] += 1
            agg[1] += e - s
            agg[2].append((s, e))
    n = max(len(rec["devices"]), 1)
    return {
        "window_s": win,
        "busy_s": sum(busy) / n,
        "devices": len(rec["devices"]),
        "programs": programs,
        "ops": ops,
        "idle_by_span": {k: sum(v) / n for k, v in gaps.items()},
        "gaps": gaps,
    }


def programs_matching(red: dict, pattern: str) -> tuple[int, float]:
    """(runs, device seconds) of the programs whose name matches."""
    rx = re.compile(pattern)
    runs, secs = 0, 0.0
    for name, (n, s, _) in red["programs"].items():
        if rx.search(name):
            runs += n
            secs += s
    return runs, secs


def ops_matching(red: dict, pattern: str) -> tuple[int, float]:
    rx = re.compile(pattern)
    runs, secs = 0, 0.0
    for name, (n, s) in red["ops"].items():
        if rx.search(name):
            runs += n
            secs += s
    return runs, secs


def breakdown(red: dict, top: int = 10) -> dict:
    """The device operations that took most time and the idle seconds by
    what the host was doing, each as [[name, seconds], ...]."""
    ops = sorted(((k, v[1]) for k, v in red["ops"].items()),
                 key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
