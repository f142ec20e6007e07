"""The four-chip cell: a tiny cell with ``chips`` 4 run whole on four CPU
devices, and what the readers make of a four-chip profiler trace (built
here as an ``XSpace``): ``collective_ms``, and the kernel's time and
roofline share, which read the same work on one chip or four."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import spec, trace
from bench.record import Record

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
KERNEL = ('%sharded_scan_agg.1 = (s32[7,1]{1,0}, f32[7,2]{1,0}) '
          'custom-call(s32[733]{0} %fusion.1), '
          'custom_call_target=\\"tpu_custom_call\\"')
ALL_REDUCE = ('%all-reduce.3 = f32[2,7]{1,0} all-reduce(f32[2,7]{1,0} '
              '%get-tuple-element.2), channel_id=1, to_apply=%add')
ALL_REDUCE_START = ('%all-reduce-start.1 = s32[7]{0} all-reduce-start('
                    's32[7]{0} %fusion.4), channel_id=2, to_apply=%add.1')
COPY = '%copy.8 = f32[210,3]{1,0} copy(f32[210,3]{0,1} %reshape.17)'

# a tiny cell asking for 4 chips, run by the tests' ``tiny_run`` on four
# CPU devices; the n_devices of every answer is kept beside the result
TINY_X4 = '''
import json, sys
sys.path[:0] = ["src", "."]
import pytest
from bench import window
from bench.tests import fixture
from bench.tests.runs import tiny_run

make_root = fixture.make_root


def with_x4(tmp, **make):
    root = make_root(tmp, **make)
    path = root + "/BENCHMARK.json"
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny-x4", "config": "tiny-one",
                               "traffic": "tiny-closed", "chips": 4,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-closed" in m.get("workloads", ()):
            m["workloads"].append("tiny-x4")
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


devices = []
records = window.query_records


def keep(win):
    out = records(win)
    devices.extend(s.ticket.result(timeout=0).stats.n_devices
                   for s, r in zip(win.sent, out) if r["answered"])
    return out


fixture.make_root, window.query_records = with_x4, keep
out = tiny_run(pytest.MonkeyPatch(), TMP, "tiny-x4", traced=True)
print("RESULT " + json.dumps({"out": out, "devices": devices}))
'''


def test_tiny_four_chip_cell_is_correct_on_four_devices(tmp_path):
    code = f"TMP = {str(tmp_path)!r}\n" + textwrap.dedent(TINY_X4)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, cwd=ROOT, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    got = json.loads(line[len("RESULT "):])
    out = got["out"]
    assert out["correct"] is True and out["failed"] == 0, out["checks"]
    assert out["device"]["count"] == 4
    assert out["metrics"]["device_answer_share"]["value"] == 100.0
    assert got["devices"] and set(got["devices"]) == {4}


def xspace(path, chips, window_us=1000):
    """Write an ``.xplane.pb`` holding one ``bench.window`` host span and,
    per chip, its ``XLA Ops`` events as (HLO text, start us, length us)."""
    from jax.profiler import ProfileData
    planes, pid = [], 0
    for c, events in enumerate(chips):
        names = sorted({t for t, _, _ in events})
        meta = "".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                       f'name: "{t}" }} }}\n' for i, t in enumerate(names))
        evs = "".join(f"events {{ metadata_id: {names.index(t) + 1} "
                      f"offset_ps: {int(a * 1e6)} "
                      f"duration_ps: {int(d * 1e6)} }}\n"
                      for t, a, d in events)
        pid += 1
        planes.append(f'planes {{ id: {pid} name: "/device:TPU:{c}" '
                      f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 '
                      f'{evs} }} {meta} }}')
    planes.append(
        f'planes {{ id: {pid + 1} name: "/host:CPU" lines {{ id: 1 '
        f'name: "main" timestamp_ns: 0 events {{ metadata_id: 1 '
        f'offset_ps: 0 duration_ps: {int(window_us * 1e6)} }} }} '
        f'event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }} }}')
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace("\n".join(planes)))
    return trace.reduce(path)


def record(summary, launches):
    return Record(loop="closed", seconds=1.0, window_start=0.0, setup_s=0.0,
                  queries=[], refreshes=[], trace=summary,
                  launches=launches)


def read(name, rec):
    return spec.metric_reader(BENCH, name)(rec)


def test_collective_ms_sums_collectives_over_chips_per_answer(tmp_path):
    # two answers; per answer each chip runs the kernel, a 5 us all-reduce
    # and a 3 us all-reduce-start, and one copy that is no collective
    chips = [[(KERNEL, 100 * q, 20), (ALL_REDUCE, 100 * q + 30, 5),
              (ALL_REDUCE_START, 100 * q + 40, 3), (COPY, 100 * q + 50, 7)]
             for q in range(2)]
    chips = [sum(chips, [])] * 4
    t = xspace(str(tmp_path / "x4.xplane.pb"), chips)
    rec = record(t, [{"least_s": 1e-6}] * 2)
    # (5 + 3) us x 4 chips x 2 answers / 2 answers
    assert read("collective_ms", rec) == pytest.approx(0.032)
    # no collective among the top ops, no device answer, no trace
    t1 = xspace(str(tmp_path / "x1.xplane.pb"),
                [[(KERNEL, 0, 20), (COPY, 30, 7)]])
    assert read("collective_ms", record(t1, [{"least_s": 1e-6}])) is None
    assert read("collective_ms", record(t, [])) is None
    assert read("collective_ms", record(None, [{"least_s": 1e-6}])) is None


def test_a_quarter_of_the_work_on_each_of_four_chips(tmp_path):
    """Each chip scanning a quarter of the rows at one chip's speed: the
    same roofline share as one chip scanning them all, and a quarter of
    its time per kernel launch."""
    launches = [{"least_s": 2e-6}, {"least_s": 2e-6}]    # whole queries
    one = xspace(str(tmp_path / "one.xplane.pb"),
                 [[(KERNEL, 0, 40), (KERNEL, 100, 40)]])
    four = xspace(str(tmp_path / "four.xplane.pb"),
                  [[(KERNEL, 0, 10), (KERNEL, 100, 10)]] * 4)
    assert four.kernel_n == 4 * one.kernel_n
    assert four.kernel_s == pytest.approx(one.kernel_s)
    assert read("fused_scan_agg_roofline", record(four, launches)) == \
        pytest.approx(read("fused_scan_agg_roofline", record(one, launches)))
    assert read("fused_scan_agg_roofline", record(one, launches)) == \
        pytest.approx(100.0 * 4e-6 / 80e-6)
    assert read("kernel_ms", record(four, launches)) == \
        pytest.approx(read("kernel_ms", record(one, launches)) / 4)
