"""What one run hands to the metric readers (``<bench>/metrics/*.py``).

Each reader is ``read(rec: Record) -> float | None``; it returns None where
the run holds nothing for it to read, and the harness then leaves the
metric out of the result line.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class Record:
    loop: str                          # 'open' | 'closed'
    seconds: float                     # length of the measured window
    window_start: float                # monotonic clock
    setup_s: float                     # process start -> window start
    # one dict per request due in the window (``window.query_records``):
    # start (due or sent), submitted, dispatched, done, latency_s,
    # answered, executed, cache_hit, coalesced, used_device, exec_s
    # (``ScanStats.latency_s``), error, cls, tenant, table
    queries: List[Dict[str, Any]]
    # one dict per refresh function: kind, seconds (first statement ->
    # flush_wal return), statements, error
    refreshes: List[Dict[str, Any]]
    # traced run only: durations (s) of the harness's host spans by name,
    # the reduced trace, and the kernel work of each device-answered query
    # (bytes, ops, least_s, bound)
    spans: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    trace: Optional[Any] = None        # trace.TraceSummary
    launches: List[Dict[str, Any]] = dataclasses.field(default_factory=list)

    @property
    def window_end(self) -> float:
        return self.window_start + self.seconds

    def answered(self) -> List[Dict[str, Any]]:
        return [q for q in self.queries if q["answered"]]

    def executed(self) -> List[Dict[str, Any]]:
        return [q for q in self.queries if q["executed"]]
