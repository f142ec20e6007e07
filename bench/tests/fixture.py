"""A small benchmark checkout for the CPU tests: the data files of
``bench/`` copied beside a ``BENCHMARK.json`` whose cells run tiny tables,
so that a whole run (set-up, window, check) takes seconds."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TINY_SF = 0.002                         # 12,002 rows, 12 blocks of 1,024


def tiny_config(name: str, tenants, sf: float = TINY_SF) -> dict:
    return {"name": name, "scale_factor": sf,
            "tables": [{"name": t, "tenant": t, "scale_factor": sf}
                       for t in tenants],
            "block_rows": 1024, "durable": True, "group_commit": 64,
            "workers": 2,
            "quotas": {t: {"latency_class": "interactive"} for t in tenants}}


def make_root(tmp: str, extra_metrics=()) -> str:
    """``tmp`` as a checkout: ``bench/`` data files plus two tiny cells,
    ``tiny-closed`` (two closed-loop streams, one table) and ``tiny-rf``
    (open loop over two tenants, one ingesting)."""
    dst = os.path.join(tmp, "bench")
    for sub in ("traffic", "queries", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(dst, sub),
                        ignore=shutil.ignore_patterns("__pycache__"),
                        dirs_exist_ok=True)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    # a traced CPU run reads no device op; the CPU entry only lets the
    # harness look its kind up
    peaks["cpu"] = peaks["TPU v5 lite"]
    with open(os.path.join(dst, "peaks.json"), "w") as f:
        json.dump(peaks, f)
    os.makedirs(os.path.join(dst, "configs"))
    cfgs = {"tiny-one": tiny_config("tiny-one", ["default"]),
            "tiny-two": tiny_config("tiny-two", ["t0", "t1"])}
    for name, cfg in cfgs.items():
        with open(os.path.join(dst, "configs", name + ".json"), "w") as f:
            json.dump(cfg, f)
    with open(os.path.join(dst, "traffic", "tiny-closed.json"), "w") as f:
        json.dump({"queries": "adhoc", "loop": "closed",
                   "streams": {"default": 2},
                   "hints": {"device_route": "collective"}}, f)
    with open(os.path.join(dst, "traffic", "tiny-rf.json"), "w") as f:
        json.dump({"queries": "adhoc", "loop": "open", "rate_qps": 4,
                   "tenants": {"t0": 0.5, "t1": 0.5},
                   "hints": {"device_route": "collective"},
                   "writes": {"tenant": "t1", "first_s": 0.2,
                              "period_s": 0.6, "functions": ["rf1", "rf2"],
                              "orders_per_sf": 10000}}, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [
        {"name": n, "source": "test", "file": f"bench/configs/{n}.json",
         "reduced": [], "why": "test"} for n in cfgs]
    bench["workloads"] = [
        {"name": "tiny-closed", "config": "tiny-one",
         "traffic": "tiny-closed", "chips": 1, "why": "test"},
        {"name": "tiny-rf", "config": "tiny-two", "traffic": "tiny-rf",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-closed", "tiny-rf"] if m["name"] not in (
                "qps", "rf_ms") else (["tiny-closed"] if m["name"] == "qps"
                                      else ["tiny-rf"])
    bench["per_layer"] += list(extra_metrics)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
