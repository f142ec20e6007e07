"""Benchmark driver: one suite per paper table/figure + the roofline table.

  PYTHONPATH=src python -m benchmarks.run [--only NAME] [--suite NAME]
                                          [--json OUT.json]

``--json`` switches to smoke mode: each selected suite that exposes a
``smoke()`` function runs a tiny-N self-checking variant (e.g. the
vectorized suite asserts pushdown ≥ 1.0× vs full decode) and the collected
metrics are written to the given JSON file, so the perf trajectory lands in
``BENCH_*.json`` across PRs.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

SUITES = [
    ("Fig8_encoding", "benchmarks.bench_encoding"),
    ("TableII_mv", "benchmarks.bench_mv"),
    ("Fig9_TableIII_vectorized", "benchmarks.bench_vectorized"),
    ("distributed_scan_fanout", "benchmarks.bench_distributed"),
    ("Fig17_update_intensive", "benchmarks.bench_update_intensive"),
    ("query_serving", "benchmarks.bench_serving"),
    ("serving_hybrid_kv", "benchmarks.bench_hybrid_kv"),
    ("roofline", "benchmarks.roofline"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="substring suite filter")
    ap.add_argument("--suite", default=None,
                    help="substring suite filter (alias of --only)")
    ap.add_argument("--json", default=None,
                    help="smoke mode: run suites' smoke() and write metrics")
    args = ap.parse_args()
    pick = args.only or args.suite
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    failures = []

    if args.json:
        results = {}
        for name, mod_name in SUITES:
            if pick and pick.lower() not in name.lower():
                continue
            mod = __import__(mod_name, fromlist=["run"])
            if not hasattr(mod, "smoke"):
                continue
            t0 = time.time()
            try:
                results[name] = mod.smoke()
                results[name]["smoke_wall_s"] = round(time.time() - t0, 3)
                print(f"[{name}] smoke ok: {results[name]}")
            except Exception as e:
                failures.append(name)
                print(f"[{name}] smoke FAILED: {e}")
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
        if failures:
            print("FAILED smoke suites:", failures)
            sys.exit(1)
        if not results:
            print(f"no suite matching {pick!r} exposes smoke(); "
                  f"available: {[n for n, _ in SUITES]}")
            sys.exit(1)
        return

    for name, mod_name in SUITES:
        if pick and pick.lower() not in name.lower():
            continue
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["run"])
            out = mod.run()
            print(out)
            print(f"[{name}] done in {time.time()-t0:.1f}s\n", flush=True)
        except Exception as e:   # keep the sweep going; report at the end
            import traceback
            failures.append(name)
            print(f"[{name}] FAILED: {e}")
            traceback.print_exc()
    if failures:
        print("FAILED suites:", failures)
        sys.exit(1)
    print("all benchmark suites completed")


if __name__ == "__main__":
    main()
