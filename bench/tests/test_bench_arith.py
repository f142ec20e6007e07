"""Whole-window arithmetic of the end-to-end readers, and the kernel's
work function."""
import os

import pytest

from bench import spec, stats, work
from bench.record import Record
from bench.tests.fixture import BENCH


def _q(start, done, **kw):
    r = {"start": start, "submitted": start, "dispatched": start + 0.001,
         "done": done, "latency_s": None if done is None else done - start,
         "answered": done is not None, "cache_hit": False,
         "coalesced": False, "used_device": True, "exec_s": 0.5,
         "error": None if done is not None else "unresolved"}
    r.update(kw)
    r["executed"] = r["answered"] and not (r["cache_hit"] or r["coalesced"])
    return r


def read(name, rec):
    return spec.metric_reader(BENCH, name)(rec)


def test_percentile_matches_linear_interpolation():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 90) == pytest.approx(4.6)
    assert stats.percentile([], 50) is None
    assert stats.percentile([7.0], 90) == 7.0


def test_latency_percentiles_span_the_whole_window():
    # two halves of a window whose own medians would average to 30 ms:
    # the window's median is the median of all 6 latencies
    early = [_q(0.0, 0.001), _q(0.1, 0.102), _q(0.2, 0.203)]
    late = [_q(5.0, 5.05), _q(5.1, 5.16), _q(5.2, 5.27)]
    rec = Record("open", 10.0, 0.0, 1.0, early + late, [])
    assert read("q_p50_ms", rec) == pytest.approx((3.0 + 50.0) / 2)
    assert read("q_p90_ms", rec) == pytest.approx(60.0 + 0.5 * 10.0)


def test_unanswered_requests_leave_the_percentiles_and_count_as_failed():
    rec = Record("open", 10.0, 0.0, 1.0,
                 [_q(0.0, 0.01), _q(0.0, None), _q(0.0, 0.03)], [])
    assert read("q_p50_ms", rec) == pytest.approx(20.0)


def test_qps_is_all_answers_over_the_time_to_the_last_one():
    qs = [_q(10.0 + i, 10.5 + i) for i in range(8)]      # last done 17.5
    rec = Record("closed", 5.0, 10.0, 1.0, qs, [])
    assert read("qps", rec) == pytest.approx(8 / 7.5)


def test_rf_ms_is_summed_time_over_count_of_acknowledged_functions():
    rfs = [{"kind": "rf1", "seconds": 0.3, "error": None, "statements": 9},
           {"kind": "rf2", "seconds": 0.5, "error": None, "statements": 9},
           {"kind": "rf1", "seconds": 9.0, "error": "KeyError: 1",
            "statements": 1}]
    rec = Record("open", 30.0, 0.0, 1.0, [], rfs)
    assert read("rf_ms", rec) == pytest.approx(400.0)
    assert read("rf_ms", Record("open", 30.0, 0.0, 1.0, [], [])) is None


def test_front_end_and_executor_readers():
    qs = [_q(0.0, 0.2, dispatched=0.004), _q(0.0, 0.3, dispatched=0.010),
          _q(0.0, 0.1, cache_hit=True), _q(0.0, 0.1, used_device=False)]
    rec = Record("open", 1.0, 0.0, 1.0, qs, [],
                 spans={"bench.stage_device": [0.2, 0.4, 0.3]})
    assert read("result_cache_hit", rec) == pytest.approx(25.0)
    assert read("device_answer_share", rec) == pytest.approx(100 * 2 / 3)
    assert read("admit_wait_ms", rec) == pytest.approx(4.0)
    assert read("execute_ms", rec) == pytest.approx(500.0)
    assert read("stage_ms", rec) == pytest.approx(300.0)
    # nothing traced: the device readers find nothing and say so
    for m in ("kernel_ms", "fused_scan_agg_roofline", "device_idle"):
        assert read(m, rec) is None


def test_kernel_work_of_sf1_q1():
    # 6,001,215 rows x (1 predicate + 2 keys + 3 values) planes x 4 B
    b, ops = work.kernel_work(6_001_215, 2, 3, 3 * 2)
    assert b == 144_029_160
    assert ops == 6_001_215 * 2 * 6 * 4
    peak = work.peaks(BENCH, "TPU v5 lite")
    t, bound = work.least_time(b, ops, peak)
    assert bound == "hbm" and t == pytest.approx(b / 819e9)


def test_unpruned_rows_follow_block_zone_maps():
    import numpy as np
    col = np.arange(10_000)                  # sorted: blocks prune
    z = work.zones(col, 1024)
    assert work.unpruned_rows(z, None, None) == 10_000
    assert work.unpruned_rows(z, 0, 1023) == 1024
    assert work.unpruned_rows(z, 9_500, None) == 10_000 - 9 * 1024
    assert work.unpruned_rows(z, 20_000, None) == 0


def test_a_device_without_peaks_is_an_error():
    with pytest.raises(KeyError):
        work.peaks(BENCH, "TPU v9 imaginary")
    assert os.path.exists(os.path.join(BENCH, "peaks.json"))
