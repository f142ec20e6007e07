"""The span recorder (``core/spans.py``) and the spans and counters of the
served query path: which spans a request opens, where they nest, which
request they carry, and the host bytes a device launch hands over."""
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import partition, pushdown, spans
from repro.core.serving import QueryServer
from repro.core.session import Database

from tests.test_pushdown import make_store
from tests.test_serving import GROUPED_Q

DEVICE_SPANS = ("ob.preamble", "ob.stage", "ob.stack", "ob.dispatch",
                "ob.wait", "ob.emit")


@pytest.fixture
def recording():
    """Recorder on for the test, off and empty after it."""
    spans.drain()
    spans.enable(True)
    try:
        yield
    finally:
        spans.enable(False)
        spans.drain()


def _spin(cpu_seconds: float) -> None:
    t = time.thread_time() + cpu_seconds
    while time.thread_time() < t:
        pass


def test_off_records_nothing_and_returns_the_shared_noop():
    spans.enable(False)
    spans.drain()
    assert spans.span("ob.a") is spans.span("ob.b") is spans.request(1)
    with spans.request(3), spans.span("ob.a"):
        with spans.span("ob.b"):
            pass
    assert spans.drain() == ([], 0)


def test_on_nests_parents_carries_the_request_and_cpu_within_wall(
        recording):
    with spans.request(7):
        with spans.span("ob.outer"):
            with spans.span("ob.inner"):
                _spin(0.01)
            time.sleep(0.02)
    with spans.span("ob.free"):
        pass
    got, dropped = spans.drain()
    by = {s.name: s for s in got}
    assert dropped == 0 and [s.name for s in got] == ["ob.inner", "ob.outer",
                                                      "ob.free"]
    assert by["ob.inner"].parent == "ob.outer"
    assert by["ob.outer"].parent is None
    assert by["ob.inner"].req == by["ob.outer"].req == 7
    assert by["ob.free"].req is None and by["ob.free"].parent is None
    for s in got:
        assert 0 <= s.cpu_ns <= s.end_ns - s.start_ns
    outer = by["ob.outer"]
    # the sleep is wall time the thread did not spend on the CPU
    assert outer.end_ns - outer.start_ns - outer.cpu_ns >= 15e6
    assert by["ob.inner"].cpu_ns >= 10e6


def test_buffer_bound_counts_drops_and_drain_empties(recording):
    for _ in range(spans.CAPACITY + 2):
        with spans.span("ob.x"):
            pass
    got, dropped = spans.drain()
    assert len(got) == spans.CAPACITY and dropped == 2
    assert spans.drain() == ([], 0)


def test_threads_keep_their_own_requests_and_parents(recording):
    # more threads than cores, switching as often as the interpreter can:
    # a lost append or a stack shared across threads breaks the counts
    n_threads, n = 16, 500

    def work(req):
        with spans.request(req):
            for _ in range(n):
                with spans.span("ob.outer"), spans.span("ob.inner"):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(r,))
                   for r in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    got, dropped = spans.drain()
    assert dropped == 0 and len(got) == n_threads * n * 2
    for r in range(n_threads):
        mine = [s for s in got if s.req == r]
        assert len(mine) == 2 * n
        assert all(s.parent == ("ob.outer" if s.name == "ob.inner" else None)
                   for s in mine)


def _serve(db, q, **hints):
    with QueryServer(db, workers=1) as srv:
        t = srv.submit(q, **hints)
        rs = t.result(timeout=300)
    return t, rs


def test_served_device_query_spans_nest_under_admit_and_execute(
        rng, recording, monkeypatch):
    monkeypatch.setattr(pushdown, "_COMPILED", {})
    db = Database(make_store(rng, dml=False), max_workers=2)
    t, rs = _serve(db, GROUPED_Q, device_route="collective")
    assert rs.stats.used_device
    assert t.submitted <= t.picked_at <= t.dispatched_at <= t.done_at
    got, dropped = spans.drain()
    assert dropped == 0
    by = {}
    for s in got:
        by.setdefault(s.name, []).append(s)
    parent = {"ob.admit": None, "ob.plan": "ob.admit", "ob.execute": None,
              "ob.kernel_compile": "ob.execute",
              "ob.stage.dicts": "ob.stage", "ob.stage.blocks": "ob.stage"}
    parent.update({n: "ob.execute" for n in DEVICE_SPANS})
    for name, p in parent.items():
        assert len(by.get(name, ())) == 1, (name, sorted(by))
        s, = by[name]
        assert s.parent == p and s.req == t.seq, s
    assert "ob.host_scan" not in by
    # the same launch shape again compiles nothing
    t2, _ = _serve(db, GROUPED_Q, device_route="collective")
    names = {s.name for s in spans.drain()[0]}
    assert set(DEVICE_SPANS) <= names and "ob.kernel_compile" not in names


def test_write_pending_query_scans_on_the_host(rng, recording):
    db = Database(make_store(rng, dml=False), max_workers=2)
    spans.drain()                      # the store's load
    h = db.table()
    h.insert({"k": 10_000, "g": 1, "d": 100, "v": 1.0, "s": "beta"})
    t, rs = _serve(db, GROUPED_Q, device_route="collective")
    assert not rs.stats.used_device and rs.stats.h2d_bytes == 0
    got, _ = spans.drain()
    writes = [s for s in got if s.name == "ob.write"]
    assert len(writes) == 1 and writes[0].req is None
    names = {s.name for s in got if s.req == t.seq}
    assert "ob.host_scan" in names and "ob.execute" in names
    assert not names & {"ob.stage", "ob.dispatch", "ob.emit"}


def test_h2d_bytes_equals_the_launch_arguments_bytes(rng, monkeypatch):
    stacked = []
    stack = partition.stack_device_stage

    def keep(*a, **k):
        out = stack(*a, **k)
        stacked.append(out[0])
        return out

    monkeypatch.setattr(partition, "stack_device_stage", keep)
    db = Database(make_store(rng, dml=False), max_workers=2)
    rs = db.query(GROUPED_Q, device_route="collective")
    assert rs.stats.used_device and len(stacked) == 1
    # deltas, bases, counts, codes, values, block mask, plus the predicate
    # bounds lo and hi as int32 scalars
    want = sum(a.nbytes for a in stacked[0]) + 2 * 4
    assert rs.stats.h2d_bytes == want
    # what is already on the device crosses nothing
    import jax.numpy as jnp
    assert pushdown.host_bytes([jnp.zeros((8, 4)), np.zeros(3, bool), 5]) \
        == 3 + 4
