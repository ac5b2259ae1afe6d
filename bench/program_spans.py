"""The program's own host spans in a profiler trace, beside the benchmark's.

The program writes `repro.<name>` annotations (`repro.core.spans`), the
benchmark `bench.<name>`; each thread has its own line in the trace's host
plane. `record(trace_dir)` keeps both kinds with their line and
attributes, as [name, start_s, dur_s, line, stats], names without their
prefix: the program's hold a dot, the benchmark's none. The loop's line is the one that holds the window span (the window
is entered on the thread that runs the training loop); the writer threads
have lines of their own.

`reduce(rec, host)` takes `bench.trace.record`'s devices and this module's
host events and gives:
  idle_by_span  the window's device-idle seconds by the innermost span on
                the loop's line (program or benchmark) at each gap's
                midpoint, the rule of `bench.trace.reduce`, found by a
                bisect over the line's spans sorted once; "other" where no
                span covers it. A writer thread's span never names a gap.
  step_gap_ms   device-idle milliseconds a step that lie in no
                `repro.train.save` and no `repro.train.drain` span: the
                host's time between steps (ROADMAP S3).
  writer_ms     the mean duration of the `repro.ckpt.write` spans that
                start in the window: the writer's time a save.
Neither reads anything from a trace whose program writes no spans.
"""
from __future__ import annotations

import bisect
import glob
import os

from bench import trace

PREFIXES = ("bench.", "repro.")
WINDOW = "window"
EXCLUDED_FROM_STEP_GAP = ("train.save", "train.drain")


def record(trace_dir: str) -> list:
    """Every `bench.` and `repro.` host event of the newest trace under
    `trace_dir`: [name, start_s, dur_s, line, stats]."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, ln in enumerate(plane.lines):
            for e in ln.events:
                if e.name.startswith(PREFIXES):
                    out.append([e.name.split(".", 1)[1],
                                e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                f"{plane.name}#{i}",
                                {k: v for k, v in e.stats}])
    return out


def is_program(name: str) -> bool:
    """The program's names hold a dot (`train.wait`), the bench's none."""
    return "." in name


def loop_line(host: list) -> str:
    lines = [e[3] for e in host if e[0] == WINDOW]
    if len(lines) != 1:
        raise ValueError(f"{len(lines)} window spans in the trace")
    return lines[0]


class Innermost:
    """The innermost span at any time, over spans of one thread (which
    nest): boundaries and the name between each two, built once."""

    def __init__(self, spans):
        self.edges: list[float] = []
        self.names: list = []
        stack: list[tuple[float, str]] = []
        for name, s, d in sorted(spans, key=lambda x: (x[1], -x[2])):
            # a span that ends before this one does is done by its start
            # (siblings may overlap by the rounding of their ends)
            while stack and stack[-1][0] < s + d:
                self._close(stack, s)
            stack.append((s + d, name))
            self._mark(s, name)
        while stack:
            self._close(stack, stack[-1][0])

    def _mark(self, t, name):
        if self.edges and self.edges[-1] == t:
            self.names[-1] = name
        else:
            self.edges.append(t)
            self.names.append(name)

    def _close(self, stack, at):
        end, _ = stack.pop()
        self._mark(min(end, at), stack[-1][1] if stack else None)

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.edges, t) - 1
        return (self.names[i] if i >= 0 else None) or "other"

    def split(self, a: float, b: float) -> dict:
        """Seconds of [a, b) under each innermost name."""
        lo = bisect.bisect_right(self.edges, a)
        hi = bisect.bisect_left(self.edges, b)
        cuts = [a] + self.edges[lo:hi] + [b]
        out: dict[str, float] = {}
        for x, y in zip(cuts, cuts[1:]):
            if y > x:
                k = self.at(x)
                out[k] = out.get(k, 0.0) + y - x
        return out


def on_loop(host: list) -> list:
    """(name, start, dur) of the loop line's spans, the window left out."""
    line = loop_line(host)
    return [(n, s, d) for n, s, d, ln, _ in host
            if ln == line and n != WINDOW]


def idle_intervals(rec: dict, t0: float, t1: float) -> dict:
    """Each device's idle [start, end) intervals inside the window."""
    out = {}
    for name, dev in rec["devices"].items():
        merged = trace._union((s, e) for _, s, e in
                              trace._clip(dev["ops"], t0, t1))
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        out[name] = [(a, b) for a, b in zip(edges[::2], edges[1::2])
                     if b > a]
    return out


def _overlap(xs, ys) -> float:
    """Total length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    tot = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            tot += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def steps_in_window(rec: dict, t0: float, t1: float) -> float:
    """Train-step program runs in the window, a device."""
    runs = sum(1 for dev in rec["devices"].values()
               for n, s, d in trace._clip(dev.get("modules", []), t0, t1)
               if "train_step" in n)
    return runs / max(len(rec["devices"]), 1)


def reduce(rec: dict, host: list) -> dict:
    t0, t1 = trace.window(rec)
    loop = on_loop(host)
    names = Innermost(loop)
    idle = idle_intervals(rec, t0, t1)
    n_dev = max(len(idle), 1)
    by_span: dict[str, float] = {}
    for gaps in idle.values():
        for a, b in gaps:
            k = names.at((a + b) / 2)
            by_span[k] = by_span.get(k, 0.0) + (b - a) / n_dev
    out = {"idle_by_span": by_span, "step_gap_ms": None, "writer_ms": None}
    if any(is_program(n) for n, _, _ in loop):
        cut = trace._union((s, s + d) for n, s, d in loop
                           if n in EXCLUDED_FROM_STEP_GAP)
        gap = sum(sum(b - a for a, b in g) - _overlap(g, cut)
                  for g in idle.values()) / n_dev
        steps = steps_in_window(rec, t0, t1)
        if steps:
            out["step_gap_ms"] = 1e3 * gap / steps
    writes = [d for n, s, d, _, _ in host
              if n == "ckpt.write" and t0 <= s < t1]
    if writes:
        out["writer_ms"] = 1e3 * sum(writes) / len(writes)
    return out
