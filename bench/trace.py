"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
kernel time, the top device operations and the longest idle gaps.

* The window is the harness's ``bench.window`` host span.
* Busy time is the union of the intervals of the ``XLA Ops`` events of each
  ``/device:TPU:<n>`` plane inside the window, averaged over the chips that
  ran any.
* The fused scan-aggregate kernel's events are the ops whose HLO text
  names a Mosaic custom call (``tpu_custom_call``); it is the only Pallas
  kernel on the served scan path.
* An idle gap is named by the innermost ``bench.*`` host span that covers
  at least half of it, ``host:unspanned`` otherwise.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
WINDOW_SPAN = "bench.window"
TOP = 10
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # averaged over the chips used
    kernel_s: float                    # summed over all kernel events
    kernel_n: int
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def op_name(text: str) -> str:
    """``<opcode>:<instruction>`` from an ``XLA Ops`` event's HLO text."""
    instr, _, rest = text.partition(" = ")
    m = _OPCODE.search(rest)
    kind = m.group(1) if m else "op"
    return f"{kind}:{instr.lstrip('%')}"


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(iv, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _name_gap(a: float, b: float,
              spans: List[Tuple[str, float, float]]) -> str:
    best: Optional[Tuple[float, str]] = None
    for name, s, e in spans:
        cover = min(b, e) - max(a, s)
        if cover >= 0.5 * (b - a) and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "host:unspanned"


def reduce(path: str) -> TraceSummary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops_by_chip: Dict[str, List[Tuple[float, float, str]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
            if evs:
                ops_by_chip[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    wins = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace {path} has no {WINDOW_SPAN} span")
    w0, w1 = wins[0]
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    busy, kernel_s, kernel_n = [], 0.0, 0
    by_op: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, str]] = []
    for chip, evs in sorted(ops_by_chip.items()):
        inside = [(a, b, n) for a, b, n in evs if b > w0 and a < w1]
        merged = _union(_clip([(a, b) for a, b, _ in inside], w0, w1))
        busy.append(sum(b - a for a, b in merged))
        for a, b, n in inside:
            by_op[op_name(n)] += (b - a) * 1e-9
            if KERNEL_MARK in n:
                kernel_s += (b - a) * 1e-9
                kernel_n += 1
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) * 1e-9, _name_gap(a, b, inner)))
    gaps.sort(key=lambda g: -g[0])
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=(sum(busy) / len(busy) * 1e-9) if busy else 0.0,
        kernel_s=kernel_s, kernel_n=kernel_n,
        device_ops=[[n, s] for n, s in top_ops],
        idle_gaps=[[n, s] for s, n in gaps[:TOP]])
