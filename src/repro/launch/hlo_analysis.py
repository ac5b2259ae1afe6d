"""Post-compile HLO analysis: collective schedule with scan correction.

XLA's cost_analysis() counts a `while` (scan) body once, not × trip-count.
This module parses the optimized HLO text of a compiled executable and:

  1. extracts every collective op (all-gather / all-reduce / reduce-scatter
     / all-to-all / collective-permute) with its result byte size,
  2. builds the computation call graph (which computation is the body of
     which while, which whiles are nested in which bodies),
  3. recovers each while's trip count from the constant in its condition
     computation (XLA scan conditions compare the induction variable
     against a literal),
  4. reports per-collective totals with each body's bytes multiplied by
     the product of trip counts along its nesting path.

The same machinery corrects FLOPs/bytes when validating the analytic
roofline model against small unrolled configs.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->", re.M)


def shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string like 'bf16[4,128,64]{2,1,0}'
    (tuples: sum of elements)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveInfo:
    kind: str
    bytes_each: int          # result bytes, one execution
    computation: str
    multiplier: int          # product of enclosing while trip counts

    @property
    def bytes_total(self) -> int:
        return self.bytes_each * self.multiplier


def _split_computations(hlo: str) -> Dict[str, str]:
    """computation name -> body text."""
    comps: Dict[str, str] = {}
    current, buf, depth = None, [], 0
    for line in hlo.splitlines():
        if current is None:
            m = _COMP_HDR.match(line.strip())
            if m and "{" in line:
                current = m.group(1)
                buf = [line]
                depth = line.count("{") - line.count("}")
                if depth <= 0:
                    comps[current] = line
                    current = None
        else:
            buf.append(line)
            depth += line.count("{") - line.count("}")
            if depth <= 0:
                comps[current] = "\n".join(buf)
                current = None
    return comps


# The while operand list may itself contain tuple shapes (nested parens),
# so match lazily up to the `condition=`/`body=` attributes on the line.
_WHILE_RE = re.compile(
    r"=\s*(\([^=]*?\)|\S+)\s+while\(.*?\)\s*,\s*condition=%?([\w\.\-]+)"
    r"\s*,\s*body=%?([\w\.\-]+)(.*)$", re.M)
_CONST_RE = re.compile(r"[su]32\[\]\s+constant\((\d+)\)")
_KTC_RE = re.compile(r"known_trip_count[^\d]*(\d+)")


def _trip_count(cond_text: str, while_line_rest: str = "") -> int:
    """Trip count of a while: XLA's `known_trip_count` backend_config when
    present, else the largest integer literal in the condition (XLA scan
    conditions compare the induction variable against a literal)."""
    m = _KTC_RE.search(while_line_rest)
    if m:
        return int(m.group(1))
    consts = [int(c) for c in _CONST_RE.findall(cond_text)]
    return max(consts) if consts else 1


def analyze_collectives(hlo: str) -> List[CollectiveInfo]:
    comps = _split_computations(hlo)

    # while structure: body -> (trip, parent computation)
    body_info: Dict[str, Tuple[int, str]] = {}
    for cname, ctext in comps.items():
        for m in _WHILE_RE.finditer(ctext):
            cond, body = m.group(2), m.group(3)
            trip = _trip_count(comps.get(cond, ""), m.group(4))
            body_info[body] = (trip, cname)

    def multiplier(comp: str) -> int:
        mult, seen = 1, set()
        cur = comp
        while cur in body_info and cur not in seen:
            seen.add(cur)
            trip, parent = body_info[cur]
            mult *= trip
            cur = parent
        return mult

    # fused computations inherit their caller's multiplier: map each
    # computation to the computation that calls it (fusion/call sites)
    callers: Dict[str, str] = {}
    call_re = re.compile(r"(?:calls=|to_apply=|fusion[^\n]*calls=)%?"
                         r"([\w\.\-]+)")
    for cname, ctext in comps.items():
        for m in call_re.finditer(ctext):
            callee = m.group(1)
            callers.setdefault(callee, cname)

    def effective_multiplier(comp: str) -> int:
        cur, seen = comp, set()
        while cur not in body_info and cur in callers and cur not in seen:
            seen.add(cur)
            cur = callers[cur]
        return multiplier(cur)

    out: List[CollectiveInfo] = []
    coll_re = re.compile(
        r"=\s*((?:\([^)]*\))|(?:[\w\[\],\{\}]+))\s+"
        r"(" + "|".join(COLLECTIVES) + r")((?:-start|-done)?)\(")
    for cname, ctext in comps.items():
        for m in coll_re.finditer(ctext):
            shape_str, kind, suffix = m.group(1), m.group(2), m.group(3)
            if suffix == "-done":
                continue      # counted at the matching -start
            b = shape_bytes(shape_str)
            if b == 0:
                continue
            out.append(CollectiveInfo(
                kind=kind, bytes_each=b, computation=cname,
                multiplier=effective_multiplier(cname)))
    return out


def collective_summary(hlo: str) -> Dict[str, int]:
    """kind -> corrected total bytes (plus 'total')."""
    infos = analyze_collectives(hlo)
    out: Dict[str, int] = defaultdict(int)
    for i in infos:
        out[i.kind] += i.bytes_total
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return dict(out)


def while_report(hlo: str) -> List[dict]:
    """Debug view: every while with its trip count."""
    comps = _split_computations(hlo)
    out = []
    for cname, ctext in comps.items():
        for m in _WHILE_RE.finditer(ctext):
            out.append({"in": cname, "body": m.group(3),
                        "trip": _trip_count(comps.get(m.group(2), ""),
                                            m.group(4))})
    return out
