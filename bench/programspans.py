"""The program's own spans (``repro.core.spans``) as a run's record holds
them: ``rec.program_spans``, one dict per span with ``name``, ``req``
(the ticket's ``seq``, None outside a request), ``parent``, ``start_ns``,
``end_ns`` and ``cpu_ns`` (the thread's own CPU time over the span).
A record without them gives every reader nothing to read."""
from __future__ import annotations

from collections import defaultdict
from typing import Any, Dict, List


def named(rec, name: str) -> List[Dict[str, Any]]:
    return [s for s in getattr(rec, "program_spans", None) or ()
            if s["name"] == name]


def wall_ms(s: Dict[str, Any]) -> float:
    return (s["end_ns"] - s["start_ns"]) * 1e-6


def per_request_ms(spans: List[Dict[str, Any]]) -> List[float]:
    """Wall time (ms) summed per request, over the spans of a request."""
    out: Dict[int, float] = defaultdict(float)
    for s in spans:
        if s["req"] is not None:
            out[s["req"]] += wall_ms(s)
    return list(out.values())
