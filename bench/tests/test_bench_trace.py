"""The reduction from a profiler trace to busy time, kernel time, device
operations and idle gaps: on synthetic intervals, and on a small trace
recorded on a TPU v5e by ``record_trace.py`` (the tests' tiny cell)."""
import gzip
import os
import random
import shutil
import time

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb.gz")


def test_union_merges_overlaps_and_clips_to_the_window():
    iv = [(0, 10), (5, 20), (30, 40), (38, 45), (50, 60)]
    assert trace._union(iv) == [(0, 20), (30, 45), (50, 60)]
    assert trace._clip(trace._union(iv), 8, 55) == [(8, 20), (30, 45),
                                                     (50, 55)]


def test_gap_takes_the_innermost_span_covering_half_of_it():
    spans = [("bench.result", 0, 100), ("bench.execute", 10, 90),
             ("bench.stage_device", 20, 60)]
    assert trace._name_gap(20, 50, spans) == "bench.stage_device"
    assert trace._name_gap(55, 85, spans) == "bench.execute"
    assert trace._name_gap(200, 300, spans) == "host:unspanned"


def test_op_names_keep_opcode_and_instruction():
    text = ('%sharded_scan_agg.1 = (s32[8,1]{1,0}, f32[8,2]{1,0}) '
            'custom-call(s32[733]{0} %fusion.1), '
            'custom_call_target="tpu_custom_call"')
    assert trace.op_name(text) == "custom-call:sharded_scan_agg.1"
    assert trace.op_name("%copy.8 = f32[210,3]{1,0} copy(f32[210,3]{0,1} "
                         "%reshape.17)") == "copy:copy.8"


def test_recorded_trace_reduces_to_consistent_numbers(tmp_path):
    assert os.path.getsize(DATA) < 1 << 20
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    t = trace.reduce(str(path))
    # the numbers of this recording (0.3 s window, 63 launches)
    assert t.kernel_n == 63
    assert t.window_s == pytest.approx(0.306816107)
    assert t.kernel_s == pytest.approx(1.75852e-4)
    assert t.busy_s == pytest.approx(4.56896e-4)
    assert t.device_ops[0][0] == "custom-call:sharded_scan_agg.1"
    assert 0 < t.busy_s < t.window_s
    assert t.kernel_n > 0 and 0 < t.kernel_s <= t.busy_s
    names = [n for n, _ in t.device_ops]
    assert any(n.startswith("custom-call:") for n in names)
    assert len(t.device_ops) <= trace.TOP and len(t.idle_gaps) <= trace.TOP
    # the device-op times cover at least the busy time's share they list,
    # and no gap is longer than the window less the busy time
    assert sum(s for _, s in t.idle_gaps) <= t.window_s - t.busy_s + 1e-9
    assert all(n.startswith(("bench.", "host:")) for n, _ in t.idle_gaps)
    assert [s for _, s in t.idle_gaps] == sorted(
        (s for _, s in t.idle_gaps), reverse=True)


def _brute(gaps, spans):
    return [trace._name_gap(a, b, spans) for a, b in gaps]


def _nested(rng, name, s, e, depth, out):
    """Spans ``name`` over ``[s, e]`` and up to ``depth`` levels of children
    inside it, as one thread's nested calls leave them."""
    out.append((name, s, e))
    t = s
    for k in range(rng.randint(0, 2) if depth else 0):
        cs = rng.randint(t, e)
        ce = rng.randint(cs, e)
        _nested(rng, f"{name}.{k}", cs, ce, depth - 1, out)
        t = ce


def _random_case(rng, offset):
    spans = []
    for thread in range(rng.randint(1, 4)):
        t = rng.randint(0, 40)
        for _ in range(rng.randint(0, 4)):
            s = t + rng.randint(0, 15)
            t = s + rng.randint(0, 60)
            _nested(rng, f"bench.t{thread}", s, t, 3, spans)
    # spans of equal length under other names, placed anywhere in the list
    for _, s, e in rng.sample(spans, min(len(spans), rng.randint(0, 3))):
        d = rng.randint(-3, 3)
        spans.insert(rng.randint(0, len(spans)), ("bench.twin", s + d, e + d))
    gaps = []
    for _chip in range(rng.randint(1, 3)):      # chips' gaps interleave
        t = rng.randint(0, 30)
        while t < 280:
            a = t + rng.randint(0, 10)
            t = a + rng.randint(1, 40)
            gaps.append((a, t))
    # gaps a span covers exactly half of, from either end
    for _, s, e in rng.sample(spans, min(len(spans), 4)):
        if e > s:
            h = rng.randint(1, e - s)
            gaps += [(s - h, s + h), (e - h, e + h)]
    if rng.random() < 0.5:
        rng.shuffle(gaps)
    return ([(a + offset, b + offset) for a, b in gaps],
            [(n, s + offset, e + offset) for n, s, e in spans])


@pytest.mark.parametrize("offset", [0, 2**53 + 12345, 2**62 + 7])
@pytest.mark.parametrize("seed", [0, 1])
def test_name_gaps_equals_name_gap_on_random_cases(seed, offset):
    rng = random.Random(seed)
    named = set()
    for _ in range(500):
        gaps, spans = _random_case(rng, offset)
        got = trace.name_gaps(gaps, spans)
        assert got == _brute(gaps, spans), (gaps, spans)
        named.update(got)
    assert "host:unspanned" in named and "bench.twin" in named
    assert any(n.count(".") > 2 for n in named)      # nested spans won


def test_name_gaps_edges():
    spans = [("bench.a", 100, 190), ("bench.b", 150, 250),
             ("bench.c", 150, 250), ("bench.d", 0, 1000)]
    gaps = [(50, 150),      # a covers exactly half: a, not d
            (200, 300),     # b and c cover exactly half, same length: b
            (250, 350),     # b and c end at its start: d
            (1000, 1200),   # d ends at its start: unspanned
            (140, 160)]     # a covers all, b and c half: a, the shortest
    want = ["bench.a", "bench.b", "bench.d", "host:unspanned", "bench.a"]
    assert trace.name_gaps(gaps, spans) == want == _brute(gaps, spans)
    assert trace.name_gaps(gaps[::-1], spans[::-1]) == [
        "bench.a", "host:unspanned", "bench.d", "bench.c", "bench.a"]
    assert trace.name_gaps([], spans) == []
    assert trace.name_gaps(gaps, []) == ["host:unspanned"] * len(gaps)


@pytest.mark.parametrize("data", ["tiny.xplane.pb.gz",
                                  "tiny_spans.xplane.pb.gz"])
def test_recorded_trace_names_gaps_as_name_gap_does(tmp_path, monkeypatch,
                                                    data):
    path = tmp_path / data[:-3]
    with gzip.open(os.path.join(os.path.dirname(DATA), data)) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    seen = []
    linear = trace.name_gaps

    def spy(gaps, spans):
        seen.append((gaps, spans))
        return linear(gaps, spans)

    monkeypatch.setattr(trace, "name_gaps", spy)
    t = trace.reduce(str(path))
    (gaps, spans), = seen
    assert t.gaps == len(gaps) > 1000 and t.spans == len(spans) > 300
    assert t.events > t.gaps
    assert linear(gaps, spans) == _brute(gaps, spans)
    monkeypatch.setattr(trace, "name_gaps", _brute)
    old = trace.reduce(str(path))
    assert t.idle_gaps == old.idle_gaps
    assert (t.device_ops, t.busy_s, t.kernel_s, t.kernel_n, t.window_s) == (
        old.device_ops, old.busy_s, old.kernel_s, old.kernel_n, old.window_s)


def test_name_gaps_scales_with_gaps_plus_spans():
    # a traced minute of a program answering thousands of queries: four
    # threads of nested spans, each query's gaps between device ops
    rng = random.Random(5)
    spans, gaps = [], []
    t = 0
    while len(spans) < 30_000:
        for thread in range(4):
            s = t + rng.randint(0, 2_000)
            e = s + rng.randint(1_000, 400_000)
            _nested(rng, f"bench.q{thread}", s, e, 2, spans)
        t += 100_000
    t = 0
    while len(gaps) < 100_000:
        a = t + rng.randint(1, 500)
        t = a + rng.randint(1, 15_000)
        gaps.append((a, t))
    t0 = time.perf_counter()
    got = trace.name_gaps(gaps, spans)
    took = time.perf_counter() - t0
    assert took < 20.0, took      # one span against every gap: ~1,000 s
    for g in rng.sample(range(len(gaps)), 100):
        assert got[g] == trace._name_gap(*gaps[g], spans)
