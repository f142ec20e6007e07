#!/usr/bin/env python
"""The control of the comparison that decides ``correct``, run by hand:

    python3 bench/control.py --workload sf1-adhoc-c4 --seeds 11 12 13

The configurations state float32 aggregates.  The control is the reference
put in the program's place and computed in the nearest precision below:
every value rounded to bfloat16, the masked group sums accumulated in
float32 on the device (a one-pass bfloat16 contraction, as a kernel that
dropped its ``HIGHEST`` precision would compute), counts exact.  Over the
queries a run of the cell sends, on the cell's own tables, it prints the
numbers the run compares; ``max_rel_err`` has to exceed its limit.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import deploy, reference, spec, traffic  # noqa: E402


def control_answer(ref: reference.RefTable, q: traffic.RefQuery,
                   ts: Optional[int] = None) -> List[Dict[str, Any]]:
    """``q`` at ``ts`` with bfloat16 values and float32 sums, on JAX's
    default device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    vals, mask, packed, size, uniq = ref.prepare(q, ts)
    seg = jnp.asarray(np.where(mask, packed, size).astype(np.int32))
    cnt = np.asarray(jax.ops.segment_sum(
        jnp.ones(seg.shape, jnp.int32), seg, num_segments=size + 1))[:size]
    sums = {}
    for c, v in vals.items():
        x = jnp.asarray(v.astype(np.float32)).astype(jnp.bfloat16)
        sums[c] = np.asarray(jax.ops.segment_sum(
            x.astype(jnp.float32), seg, num_segments=size + 1),
            np.float64)[:size]
    return reference.emit(q, uniq, cnt, sums)


def control_numbers(cell: spec.Cell, seed: int, n_queries: int
                    ) -> Dict[str, float]:
    """The compared numbers of the control over the first ``n_queries``
    requests a run of ``cell`` with ``seed`` sends, on the base tables."""
    refs = {t["name"]: reference.RefTable(deploy.generate(t, seed, k))
            for k, t in enumerate(cell.config["tables"])}
    tenants = {t["tenant"]: t["name"] for t in cell.config["tables"]}
    sched = traffic.Schedule(cell.traffic, cell.queries, tenants, seed,
                             cell.run_seconds)
    worst, wrong = 0.0, 0
    for i in sched.first(n_queries):
        it = sched.item(i)
        ref = refs[it.table]
        diff, err = reference.compare(it.ref, control_answer(ref, it.ref),
                                      ref.answer(it.ref))
        wrong += diff is not None
        worst = max(worst, err)
    return {"max_rel_err": worst, "wrong_answers": wrong,
            "unanswered": 0, "lost_writes": 0}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--queries", type=int, default=40,
                    help="requests per seed, from the start of the schedule")
    args = ap.parse_args()
    import jax
    dev = jax.devices()[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind}",
          flush=True)
    cell = spec.load_cell(ROOT, args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        nums = control_numbers(cell, seed, args.queries)
        print(f"[control] workload={args.workload} seed={seed} "
              f"queries={args.queries} max_rel_err={nums['max_rel_err']} "
              f"limit={reference.LIMITS['max_rel_err']} "
              f"wrong_answers={nums['wrong_answers']} "
              f"fails={not reference.verdict(nums)} "
              f"seconds={time.perf_counter() - t0:.1f}", flush=True)


if __name__ == "__main__":
    main()
