"""A cell, a configuration, a mix and a per-layer metric are added by new
files and entries alone: the harness finds each by its name."""
import json
import os

from bench.tests.runs import tiny_run


def test_new_files_and_entries_need_no_harness_edit(monkeypatch, tmp_path):
    extra = [{"name": "answered_count", "unit": "queries", "better": "higher",
              "source": "program_counter", "layer": "serving front end",
              "moves": "q_p50_ms", "workloads": ["tiny-closed"]}]
    # a metric reader of its own, dropped beside the others
    mdir = tmp_path / "bench" / "metrics"
    os.makedirs(mdir)
    (mdir / "answered_count.py").write_text(
        "def read(rec):\n    return float(len(rec.answered()))\n")
    out = tiny_run(monkeypatch, tmp_path, "tiny-closed", traced=True,
                   extra_metrics=extra)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["answered_count"]["value"] == out["attempted"]
    assert out["metrics"]["device_answer_share"]["value"] == 100.0
    assert list(out)[-1] == "checks"
    # the fixture's own configuration, mix and cell were found by name
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    assert {w["config"] for w in bench["workloads"]} == {"tiny-one",
                                                         "tiny-two"}
