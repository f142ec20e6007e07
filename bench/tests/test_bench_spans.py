"""Readers of the program's own spans and counters, on synthetic records
and on tiny CPU runs through ``bench/run_spans.py``, and idle gaps named
by the program's spans."""
import gzip
import os
import shutil

import pytest

from bench import run_spans, spec, stats, trace
from bench.record import Record
from bench.tests.fixture import BENCH
from bench.tests.runs import tiny_run

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tiny_spans.xplane.pb.gz")


def read(name, rec):
    return spec.metric_reader(BENCH, name)(rec)


def _q(**kw):
    r = {"start": 0.0, "submitted": 0.0, "picked": 0.002,
         "dispatched": 0.005, "done": 1.0, "latency_s": 1.0,
         "answered": True, "cache_hit": False, "coalesced": False,
         "used_device": True, "exec_s": 0.9, "error": None,
         "h2d_bytes": 96_000_000}
    r.update(kw)
    r["executed"] = r["answered"] and not (r["cache_hit"] or r["coalesced"])
    return r


def _s(name, req, ms, cpu_ms=0.0, parent=None):
    return {"name": name, "req": req, "parent": parent, "start_ns": 0,
            "end_ns": int(ms * 1e6), "cpu_ns": int(cpu_ms * 1e6)}


def test_program_span_and_counter_readers():
    qs = [_q(picked=0.001), _q(picked=0.003),
          _q(picked=0.010, used_device=False, h2d_bytes=0),
          _q(picked=0.500, cache_hit=True),           # not executed
          _q(picked=None)]                            # never picked
    rec = Record("closed", 1.0, 0.0, 1.0, qs, [])
    rec.program_spans = [
        # request 1 was planned twice (deferred, then re-admitted)
        _s("ob.plan", 1, 2.0), _s("ob.plan", 1, 1.0), _s("ob.plan", 2, 4.0),
        _s("ob.plan", 3, 1.0),
        _s("ob.stage", 1, 800.0, cpu_ms=150.0),
        _s("ob.stage", 2, 900.0, cpu_ms=250.0),
        _s("ob.stage", 3, 700.0, cpu_ms=200.0),
        _s("ob.dispatch", 1, 10.0), _s("ob.dispatch", 2, 30.0),
        _s("ob.emit", 1, 0.2), _s("ob.emit", 2, 0.4), _s("ob.emit", 3, 0.3),
        # request 4 scanned its shards twice on the host (a retried run)
        _s("ob.host_scan", 4, 1000.0), _s("ob.host_scan", 4, 500.0),
        _s("ob.host_scan", 5, 2000.0),
        _s("ob.write", None, 0.1), _s("ob.write", None, 0.3),
        _s("ob.write", None, 0.2), _s("ob.wal_flush", None, 5.0, 0.1,
                                      parent="ob.write"),
    ]
    assert read("queue_wait_ms", rec) == pytest.approx(3.0)
    assert read("plan_ms", rec) == pytest.approx(3.0)
    assert read("stage_cpu_ms", rec) == pytest.approx(200.0)
    assert read("h2d_mb", rec) == pytest.approx(96.0)
    assert read("dispatch_ms", rec) == pytest.approx(20.0)
    assert read("emit_ms", rec) == pytest.approx(0.3)
    assert read("host_scan_ms", rec) == pytest.approx(1750.0)
    assert read("write_ms", rec) == pytest.approx(0.2)


def test_readers_find_nothing_without_program_spans():
    # a record of a harness or a program that does not record them
    qs = [{k: v for k, v in _q().items() if k not in ("picked", "h2d_bytes")}]
    rec = Record("closed", 1.0, 0.0, 1.0, qs, [])
    for name in run_spans.METRICS:
        assert read(name, rec) is None, name


def test_gap_takes_an_ob_span_inside_a_bench_span():
    spans = [("bench.execute", 0, 100), ("ob.execute", 5, 95),
             ("bench.stage_device", 10, 60), ("ob.stage", 11, 59),
             ("ob.stage.blocks", 20, 58), ("ob.wait", 70, 72)]
    assert trace._name_gap(25, 55, spans) == "ob.stage.blocks"
    assert trace._name_gap(12, 20, spans) == "ob.stage"
    assert trace._name_gap(62, 90, spans) == "ob.execute"


def test_tiny_device_window_reads_six_program_metrics(monkeypatch, tmp_path):
    got = {}
    with run_spans.wrapped(got):
        out = tiny_run(monkeypatch, tmp_path, "tiny-closed", traced=True)
    assert out["correct"] is True, out["checks"]
    n = run_spans.numbers(got)
    assert n["dropped"] == 0
    assert set(n["program"]) == set(run_spans.METRICS) - {"host_scan_ms",
                                                          "write_ms"}
    # 12 blocks of 1,024 rows: the stacked planes of a ship-mode year (one
    # key, two values: 16 B a row) or of a Q1 (two keys, three values)
    assert 12 * 1024 * 16 / 1e6 <= n["program"]["h2d_mb"] <= \
        12 * 1024 * 24 / 1e6 + 1e-3
    for name, v in n["program"].items():
        assert v > 0, name
    # the harness's own numbers are read as before, and its span around
    # stage_device holds the program's ob.stage, call for call
    assert "stage_ms" in out["metrics"] and "admit_wait_ms" in out["metrics"]
    stage = [s for s in got["spans"] if s.name == "ob.stage"]
    assert len(stage) == len(got["rec"].spans["bench.stage_device"])
    assert stats.median([(s.end_ns - s.start_ns) * 1e-6 for s in stage]) \
        <= out["metrics"]["stage_ms"]["value"]


def test_tiny_ingest_window_reads_host_scans_and_writes(monkeypatch,
                                                        tmp_path):
    got = {}
    with run_spans.wrapped(got):
        out = tiny_run(monkeypatch, tmp_path, "tiny-rf", seconds=3.0)
    assert out["correct"] is True, out["checks"]
    n = run_spans.numbers(got)
    assert n["program"]["host_scan_ms"] > 0
    assert n["program"]["write_ms"] > 0
    names = {s.name for s in got["spans"]}
    assert "ob.wal_flush" in names


def test_recorded_trace_holds_program_spans_on_the_device_clock(tmp_path):
    # recorded on a TPU v5e by record_spans_trace.py (the tests' tiny cell)
    assert os.path.getsize(DATA) < 1 << 20
    path = str(tmp_path / "tiny_spans.xplane.pb")
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    from jax.profiler import ProfileData
    host, kernels = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for line in plane.lines for ev in line.events
                     if ev.name.startswith(run_spans.PREFIXES)]
        elif plane.name.startswith("/device:TPU:"):
            kernels += [(ev.start_ns, ev.start_ns + ev.duration_ns)
                        for line in plane.lines if line.name == "XLA Ops"
                        for ev in line.events if trace.KERNEL_MARK in ev.name]
    (w0, w1), = [(s, e) for n, s, e in host if n == trace.WINDOW_SPAN]
    ob = [sp for sp in host if sp[0].startswith("ob.")]
    assert {"ob.admit", "ob.plan", "ob.execute", "ob.preamble", "ob.stage",
            "ob.stage.blocks", "ob.stack", "ob.dispatch", "ob.wait",
            "ob.emit"} <= {n for n, _, _ in ob}
    assert all(w0 <= s and e <= w1 for _, s, e in ob)
    # one clock: every kernel in the window ran inside some ob.execute
    execs = [(s, e) for n, s, e in ob if n == "ob.execute"]
    inside = [(a, b) for a, b in kernels if w0 <= a and b <= w1]
    assert inside
    assert all(any(s <= a and b <= e for s, e in execs) for a, b in inside)
    gaps = run_spans.idle_gaps(path)
    assert any(n.startswith("ob.") for n, _ in gaps)
    # the harness's reduction reads it as it reads any trace
    t = trace.reduce(path)
    assert 0 < t.kernel_s <= t.busy_s < t.window_s and t.kernel_n > 0
    assert all(n.startswith(("bench.", "host:")) for n, _ in t.idle_gaps)
