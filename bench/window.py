"""The measured window: requests through ``QueryServer.submit`` →
``Ticket.result``, the refresh stream beside them, and the record that the
metric readers and the comparison read afterwards.

Latency runs from when a request was due (open loop) or sent (closed
loop) to when its ticket resolved.  Every request due in the window is
waited for, up to ``DRAIN_S`` past its close: one answered late is late,
one that never resolves is unanswered.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional

from .traffic import Item, Refresh, Schedule

DRAIN_S = 60.0


class CompileClock:
    """Counts JAX's backend compiles and their seconds, from any thread."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if "backend_compile" in event:
            with self._lock:
                self.count += 1
                self.seconds += duration


class Spans:
    """Host spans the harness opens around the program's layers in a traced
    run, from its own files: ``bench.execute`` (``Database.execute``),
    ``bench.stage_device`` (``pushdown.stage_device``) and
    ``bench.stack_device_stage`` (``partition.stack_device_stage``), plus
    ``bench.submit`` and ``bench.result`` opened by the drivers.  Each is a
    ``jax.profiler.TraceAnnotation``, so it lands in the profiler's trace on
    the device's clock; stage durations are also kept here."""

    def __init__(self, on: bool):
        self.on = on
        self.durations: Dict[str, List[float]] = {}
        self._lock = threading.Lock()
        self._undo: List[Any] = []

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _wrap(self, owner, attr: str, name: str) -> None:
        orig = getattr(owner, attr)
        spans = self

        def wrapped(*a, **k):
            t0 = time.perf_counter()
            with spans(name):
                out = orig(*a, **k)
            dt = time.perf_counter() - t0
            with spans._lock:
                spans.durations.setdefault(name, []).append(dt)
            return out

        setattr(owner, attr, wrapped)
        self._undo.append((owner, attr, orig))

    def install(self) -> None:
        if not self.on:
            return
        from repro.core import partition, pushdown
        from repro.core.session import Database
        self._wrap(Database, "execute", "bench.execute")
        self._wrap(pushdown, "stage_device", "bench.stage_device")
        self._wrap(partition, "stack_device_stage", "bench.stack_device_stage")

    def uninstall(self) -> None:
        for owner, attr, orig in reversed(self._undo):
            setattr(owner, attr, orig)
        self._undo.clear()


@dataclasses.dataclass
class Sent:
    """One request as sent: its schedule item, when it was due or sent,
    and its ticket (None where ``submit`` itself raised)."""

    item: Item
    start: float                       # monotonic: due (open) / sent (closed)
    ticket: Any = None
    error: Optional[str] = None


@dataclasses.dataclass
class Applied:
    """One refresh function as applied: its statements with the snapshot
    timestamp each returned, acknowledged by ``flush_wal``."""

    kind: str
    table: str
    start: float
    end: float
    inserts: List[Any]                 # (ts, row)
    deletes: List[Any]                 # (ts, pk)
    error: Optional[str] = None


@dataclasses.dataclass
class Window:
    start: float
    seconds: float
    loop: str
    sent: List[Sent]
    applied: List[Applied]
    lateness_s: List[float]            # open loop: send time - due time

    @property
    def end(self) -> float:
        return self.start + self.seconds


def _submit(srv, item: Item, hints: Dict[str, Any], span) -> Any:
    with span("bench.submit"):
        return srv.submit(item.query(), item.table, tenant=item.tenant,
                          **hints)


def _wait(t, deadline: float, span) -> Optional[str]:
    """Block on a ticket until ``deadline``; the error text, if any."""
    try:
        with span("bench.result"):
            t.result(timeout=max(0.0, deadline - time.monotonic()))
    except TimeoutError:
        return "unresolved"
    # lint: allow(broad-except) — a query's own failure is recorded and
    # counted as unanswered; the window goes on
    except Exception as e:             # noqa: BLE001
        return f"{type(e).__name__}: {e}"
    return None


def _writer(dep, refreshes: List[Refresh], t0: float,
            out: List[Applied]) -> None:
    for rf in refreshes:
        delay = t0 + rf.at_s - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        h = dep.handles[rf.table]
        ins, dels = [], []
        start = time.monotonic()
        err = None
        try:
            for row in rf.rows:
                ins.append((h.insert(row), row))
            for pk in rf.pks:
                dels.append((h.delete(pk), pk))
            dep.db.flush_wal()
        # lint: allow(broad-except) — a failed statement ends the stream;
        # it is recorded and fails the run
        except Exception as e:         # noqa: BLE001
            err = f"{type(e).__name__}: {e}"
        out.append(Applied(rf.kind, rf.table, start, time.monotonic(),
                           ins, dels, err))
        if err:
            return


def run(srv, dep, sched: Schedule, refreshes: List[Refresh],
        seconds: float, span) -> Window:
    """Drive one window and wait for every request due in it."""
    sent: List[Sent] = []
    applied: List[Applied] = []
    lateness: List[float] = []
    lock = threading.Lock()
    t0 = time.monotonic()
    end = t0 + seconds
    threads = []
    if refreshes:
        threads.append(threading.Thread(
            target=_writer, args=(dep, refreshes, t0, applied),
            name="bench-writer"))
    if sched.loop == "closed":
        def stream(queue) -> None:
            while True:
                with lock:
                    i = next(queue, None)
                if i is None or time.monotonic() >= end:
                    return
                item = sched.item(i)
                sn = Sent(item, time.monotonic())
                try:
                    sn.ticket = _submit(srv, item, sched.hints, span)
                # lint: allow(broad-except) — a refused submit is recorded
                # as unanswered
                except Exception as e:     # noqa: BLE001
                    sn.error = f"{type(e).__name__}: {e}"
                else:
                    sn.error = _wait(sn.ticket, end + DRAIN_S, span)
                with lock:
                    sent.append(sn)

        for t, (idx, m) in enumerate(sched.queues):
            queue = iter(idx)
            threads += [threading.Thread(target=stream, args=(queue,),
                                         name=f"bench-stream{t}.{s}")
                        for s in range(m)]
    else:
        def dispatch() -> None:
            for i in range(len(sched)):
                item = sched.item(i)
                due = t0 + item.at_s
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                s = Sent(item, due)
                lateness.append(time.monotonic() - due)
                try:
                    s.ticket = _submit(srv, item, sched.hints, span)
                # lint: allow(broad-except) — a refused submit is recorded
                # as unanswered
                except Exception as e:     # noqa: BLE001
                    s.error = f"{type(e).__name__}: {e}"
                sent.append(s)

        threads.append(threading.Thread(target=dispatch,
                                        name="bench-dispatch"))
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    for s in sent:
        if s.ticket is not None and s.error is None:
            s.error = _wait(s.ticket, end + DRAIN_S, span)
    sent.sort(key=lambda s: s.item.index)
    return Window(t0, seconds, sched.loop, sent, applied, lateness)


def query_records(win: Window) -> List[Dict[str, Any]]:
    """One plain record per request, for the metric readers."""
    out = []
    for s in win.sent:
        t = s.ticket
        r: Dict[str, Any] = {
            "index": s.item.index, "cls": s.item.cls, "tenant": s.item.tenant,
            "table": s.item.table, "start": s.start, "error": s.error,
            "submitted": None if t is None else t.submitted,
            "dispatched": None if t is None else t.dispatched_at,
            "done": None if t is None else t.done_at,
            "cache_hit": bool(t is not None and t.cache_hit),
            "coalesced": bool(t is not None and t.coalesced),
        }
        r["answered"] = s.error is None and r["done"] is not None
        r["latency_s"] = (r["done"] - s.start) if r["answered"] else None
        r["executed"] = r["answered"] and not (r["cache_hit"]
                                               or r["coalesced"])
        st = t.result(timeout=0).stats if r["answered"] else None
        r["used_device"] = bool(st is not None and st.used_device)
        r["exec_s"] = None if st is None else st.latency_s
        out.append(r)
    return out
