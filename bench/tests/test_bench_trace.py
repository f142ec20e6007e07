"""The reduction from a profiler trace to busy time, kernel time, device
operations and idle gaps: on synthetic intervals, and on a small trace
recorded on a TPU v5e by ``record_trace.py`` (the tests' tiny cell)."""
import gzip
import os
import shutil

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
