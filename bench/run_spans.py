#!/usr/bin/env python
"""A run of one cell with the program's own spans on, and what they read.

    python3 bench/run_spans.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run is ``bench/run.py``'s, with the program's span recorder
(``repro.core.spans``) on through the measured window.  Until the harness
turns the recorder on itself (PERF.md §7), this wraps four of its names
for the one run: ``window.run`` (recorder on for the window, drained
after), ``window.query_records`` (adds each ticket's ``picked_at`` and
its ``ScanStats.h2d_bytes``), ``run.Record`` (keeps the record) and, in a
traced run, ``trace.reduce`` (also names the idle gaps by ``ob.*``
spans).  With ``--trace 0`` the profiler is off and only the recorder
runs: the end-to-end numbers then show what the spans cost.

Prints the harness's result line, then one JSON object: ``program``, the
numbers of the readers in ``METRICS``; ``span_ms``, for each span name
its count and median, summed wall and summed CPU milliseconds;
``idle_gaps`` (traced runs), the longest gaps named by ``bench.*`` and
``ob.*`` host spans; ``spans`` and ``dropped``, the recorder's counts.
"""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(os.path.dirname(BENCH), "src"),
           os.path.dirname(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run, spec, stats, trace, window  # noqa: E402

METRICS = ("queue_wait_ms", "plan_ms", "stage_cpu_ms", "h2d_mb",
           "dispatch_ms", "emit_ms", "host_scan_ms", "write_ms")
PREFIXES = ("bench.", "ob.")


def idle_gaps(path: str) -> List[List[Any]]:
    """``trace.reduce``'s idle gaps of the ``.xplane.pb`` at ``path``, each
    named by the innermost ``bench.*`` or ``ob.*`` host span covering half
    of it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, chips = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            iv = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                  for line in plane.lines if line.name == "XLA Ops"
                  for ev in line.events]
            if iv:
                chips.append(iv)
        elif plane.name.startswith("/host:"):
            spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                      for line in plane.lines for ev in line.events
                      if ev.name.startswith(PREFIXES)]
    w0, w1 = next((s, e) for n, s, e in spans if n == trace.WINDOW_SPAN)
    inner = [sp for sp in spans if sp[0] != trace.WINDOW_SPAN]
    idle = []
    for iv in chips:
        merged = trace._union(trace._clip(iv, w0, w1))
        edges = [w0] + [x for m in merged for x in m] + [w1]
        idle += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps = [((b - a) * 1e-9, n)
            for (a, b), n in zip(idle, trace.name_gaps(idle, inner))]
    gaps.sort(key=lambda g: -g[0])
    return [[n, s] for s, n in gaps[:trace.TOP]]


@contextlib.contextmanager
def wrapped(got: Dict[str, Any], keep_trace: Optional[str] = None):
    """Runs of ``run.run_cell`` inside record the program's spans into
    ``got``; ``keep_trace`` copies a traced run's ``.xplane.pb`` there."""
    from repro.core import spans
    orig = (window.run, window.query_records, run.Record,
            run.tracemod.reduce)

    def windowed(*a, **k):
        spans.drain()
        spans.enable(True)
        try:
            return orig[0](*a, **k)
        finally:
            spans.enable(False)
            got["spans"], got["dropped"] = spans.drain()

    def records(win):
        out = orig[1](win)
        for s, r in zip(win.sent, out):
            st = s.ticket.result(timeout=0).stats if r["answered"] else None
            r["picked"] = None if s.ticket is None else s.ticket.picked_at
            r["h2d_bytes"] = None if st is None else st.h2d_bytes
        return out

    def record(*a, **k):
        got["rec"] = orig[2](*a, **k)
        return got["rec"]

    def reduce(path):
        if keep_trace:
            shutil.copy(path, keep_trace)
        got["idle_gaps"] = idle_gaps(path)
        return orig[3](path)

    window.run, window.query_records = windowed, records
    run.Record, run.tracemod.reduce = record, reduce
    try:
        yield got
    finally:
        window.run, window.query_records, run.Record, \
            run.tracemod.reduce = orig


def numbers(got: Dict[str, Any]) -> Dict[str, Any]:
    """The readers' numbers of a run recorded by ``wrapped``."""
    rec = got["rec"]
    rec.program_spans = [s._asdict() for s in got["spans"]]
    program = {}
    for name in METRICS:
        v = spec.metric_reader(BENCH, name)(rec)
        if v is not None:
            program[name] = v
    by: Dict[str, List[Any]] = {}
    for s in got["spans"]:
        by.setdefault(s.name, []).append(s)
    span_ms = {n: [len(v), stats.median([(s.end_ns - s.start_ns) * 1e-6
                                         for s in v]),
                   sum(s.end_ns - s.start_ns for s in v) * 1e-6,
                   sum(s.cpu_ns for s in v) * 1e-6]
               for n, v in sorted(by.items())}
    out = {"program": program, "span_ms": span_ms, "spans": len(got["spans"]),
           "dropped": got["dropped"]}
    if "idle_gaps" in got:
        out["idle_gaps"] = got["idle_gaps"]
    return out


def main(argv: Optional[List[str]] = None) -> int:
    got: Dict[str, Any] = {}
    with wrapped(got):
        rc = run.main(argv)
    if rc == 0:
        print(json.dumps(numbers(got)), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
