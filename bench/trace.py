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
  at least half of it, ``host:unspanned`` otherwise: ``name_gaps``, one
  sweep over gaps and spans in time order, so that a traced run of a fast
  program, with tens of thousands of each, is named in seconds.
  ``_name_gap``, one gap against every span, is the tests' oracle.
"""
from __future__ import annotations

import dataclasses
import heapq
import re
import time
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
    # the reduction's own cost, for the run's log: seconds to parse the
    # file, to walk its events and to name the gaps; events walked, gaps
    # named and ``bench.*`` spans they were named from
    parse_s: float
    walk_s: float
    name_s: float
    events: int
    gaps: int
    spans: int


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


def name_gaps(gaps: List[Tuple[float, float]],
              spans: List[Tuple[str, float, float]]) -> List[str]:
    """``_name_gap(a, b, spans)`` of every gap ``(a, b)`` with ``a <= b``,
    in O((G + S) log(G + S) + G * A) for G gaps, S spans and at most A
    spans open at once (threads times nesting).

    A span covers half of ``[a, b]`` only if it holds the midpoint, so the
    gaps are taken in midpoint order while a heap by end keeps the spans
    open there; only those are tested.  Comparisons stay in the inputs'
    own arithmetic, doubled rather than halved: exact for integer
    timestamps of any size.  Equal lengths go to the span earliest in
    ``spans``, as in ``_name_gap``."""
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    names = ["host:unspanned"] * len(gaps)
    open_: List[Tuple[float, int]] = []         # (end, index in spans)
    k = 0
    for g in sorted(range(len(gaps)), key=lambda g: gaps[g][0] + gaps[g][1]):
        a, b = gaps[g]
        mid2 = a + b
        while k < len(order) and 2 * spans[order[k]][1] <= mid2:
            i = order[k]
            heapq.heappush(open_, (spans[i][2], i))
            k += 1
        while open_ and 2 * open_[0][0] < mid2:
            heapq.heappop(open_)
        best = None
        for e, i in open_:
            s = spans[i][1]
            if 2 * (min(b, e) - max(a, s)) >= b - a and (
                    best is None or (e - s, i) < best):
                best = (e - s, i)
        if best is not None:
            names[g] = spans[best[1]][0]
    return names


def reduce(path: str) -> TraceSummary:
    from jax.profiler import ProfileData
    t0 = time.perf_counter()
    pd = ProfileData.from_file(path)
    t1 = time.perf_counter()
    ops_by_chip: Dict[str, List[Tuple[float, float, str]]] = {}
    spans: List[Tuple[str, float, float]] = []
    events = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
            events += len(evs)
            if evs:
                ops_by_chip[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    events += 1
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
    idle: List[Tuple[float, float]] = []
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
        idle += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    t2 = time.perf_counter()
    gaps = [((b - a) * 1e-9, n)
            for (a, b), n in zip(idle, name_gaps(idle, inner))]
    t3 = time.perf_counter()
    gaps.sort(key=lambda g: -g[0])
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=(sum(busy) / len(busy) * 1e-9) if busy else 0.0,
        kernel_s=kernel_s, kernel_n=kernel_n,
        device_ops=[[n, s] for n, s in top_ops],
        idle_gaps=[[n, s] for s, n in gaps[:TOP]],
        parse_s=t1 - t0, walk_s=t2 - t1, name_s=t3 - t2, events=events,
        gaps=len(idle), spans=len(inner))
