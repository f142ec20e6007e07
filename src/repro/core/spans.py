"""Spans of the served query path: named, nested intervals of one
request's work, with the thread's own CPU time beside the wall time.

    with spans.span("ob.stage"):
        ...

Off (the default), ``span`` and ``request`` return one shared no-op
context: no allocation, no clock read, no lock.  On (``enable(True)``),
each span

* opens a ``jax.profiler.TraceAnnotation`` named like the span and
  carrying the request id, so a running profiler puts it in its host
  plane on the device trace's clock;
* appends a :class:`Span` to a bounded in-memory buffer (spans past the
  bound are counted as dropped, not kept);
* reads ``time.thread_time_ns`` at both ends: ``cpu_ns`` is the CPU the
  thread itself spent in the span, against its wall time.

``req`` is the request id that ``request`` set on the thread
(``QueryServer``: the ticket's ``seq``, on the scheduler thread while it
admits the ticket and on the worker while it runs it), None outside a
request; ``parent`` is the name of the enclosing span on the same thread.
``drain`` hands the buffer over and empties it.
"""
from __future__ import annotations

import threading
import time
from typing import Any, List, NamedTuple, Optional, Tuple

__all__ = ["Span", "span", "request", "enable", "drain"]

CAPACITY = 1 << 16                     # spans kept between two drains


class Span(NamedTuple):
    name: str
    req: Optional[int]
    parent: Optional[str]
    start_ns: int                      # time.monotonic_ns
    end_ns: int
    cpu_ns: int                        # time.thread_time_ns over the span


class _Off:
    """The shared context every call returns while tracing is off."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc: Any) -> bool:
        return False


class _Buffer:
    """Bounded span store shared by every thread."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self._dropped = 0

    def add(self, s: Span) -> None:
        with self._lock:
            if len(self._spans) < CAPACITY:
                self._spans.append(s)
            else:
                self._dropped += 1

    def take(self) -> Tuple[List[Span], int]:
        with self._lock:
            out, dropped = self._spans, self._dropped
            self._spans, self._dropped = [], 0
        return out, dropped


_OFF = _Off()
_BUF = _Buffer()
_LOCAL = threading.local()             # .req, .stack (open span names)
_on = False
_annotation: Any = None                # jax.profiler.TraceAnnotation


def _stack() -> List[str]:
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


class _Open:
    """One span being recorded (tracing on)."""

    __slots__ = ("name", "req", "parent", "ann", "t0", "c0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> None:
        st = _stack()
        self.parent = st[-1] if st else None
        self.req = getattr(_LOCAL, "req", None)
        st.append(self.name)
        self.ann = (_annotation(self.name) if self.req is None
                    else _annotation(self.name, req=self.req))
        self.ann.__enter__()
        # the CPU reads sit inside the wall reads: cpu_ns <= wall
        self.t0 = time.monotonic_ns()
        self.c0 = time.thread_time_ns()

    def __exit__(self, *exc: Any) -> bool:
        c1 = time.thread_time_ns()
        t1 = time.monotonic_ns()
        self.ann.__exit__(*exc)
        _stack().pop()
        _BUF.add(Span(self.name, self.req, self.parent, self.t0, t1,
                      c1 - self.c0))
        return False


class _Request:
    """The request id of the spans this thread opens inside it."""

    __slots__ = ("req", "prev")

    def __init__(self, req: int):
        self.req = req

    def __enter__(self) -> None:
        self.prev = getattr(_LOCAL, "req", None)
        _LOCAL.req = self.req

    def __exit__(self, *exc: Any) -> bool:
        _LOCAL.req = self.prev
        return False


def span(name: str):
    """Context that records ``name`` over its extent while tracing is on."""
    return _Open(name) if _on else _OFF


def request(req: int):
    """Context inside which this thread's spans carry request id ``req``."""
    return _Request(req) if _on else _OFF


def enable(on: bool) -> None:
    """Turn recording on or off."""
    global _on, _annotation
    if on and _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    _on = bool(on)


def drain() -> Tuple[List[Span], int]:
    """The spans recorded since the last drain, oldest first, and the
    number dropped past the bound; empties the buffer."""
    return _BUF.take()
