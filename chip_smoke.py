#!/usr/bin/env python
"""Chip smoke: the served scan path on a TPU, end to end, at TPC-H SF 1.

    python chip_smoke.py [--seed N]      # one chip, all phases
    python chip_smoke.py --chips 4       # the 4-chip collective

One process drives everything (a chip belongs to one process).  Phases:

1. device: platform, kind and count; anything but a TPU exits non-zero.
2. load: TPC-H ``lineitem`` generated from ``--seed`` (``benchmarks/tpch.py``)
   and direct-loaded with ``bulk_insert`` into a durable ``Database``
   (write-ahead log, group commit).
3. serve: Q1, Q1 with a top-k ``limit`` and a one-year ship-mode window go
   through ``QueryServer.submit(..., device_route="collective")``.  Every
   answer must have run on the device, with nothing degraded or repaired,
   and match ``VectorEngine`` over fully decoded columns: keys and counts
   exactly, float aggregates within ``RTOL``.
4. write: inserts, updates and deletes through the table's DML (logged),
   ``flush_wal``, ``major_compact``, a read-back of every kind of write, and
   phase 3 again over the new baseline.

Wall and compile seconds are printed as information, never as metrics.
With ``--chips 4`` only Q1 runs, through ``submit(n_shards=4,
device_route="collective")``, and must span four devices.  Any failed check
raises; the last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks import tpch  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import pushdown  # noqa: E402
from repro.core.engine import VectorEngine  # noqa: E402
from repro.core.serving import QueryServer  # noqa: E402
from repro.core.session import Database  # noqa: E402

# Device sums are f32 (values are staged as f32 and folded into one f32
# accumulator over the kernel's sequential grid steps, ROADMAP debt C5)
# while the host reference sums in f64.  Over SF 1's 5,861 blocks the
# worst-case bound m * 2^-24 is 3.5e-4, but the per-step rounding errors
# behave like a random walk, about sqrt(m) * 2^-24 = 4.6e-6: the largest
# error seen on a TPU v5e is 2.9e-6.  2e-5 keeps a 7x margin over that and
# still fails a lost or doubled block (1/5,861 = 1.7e-4) or a one-pass bf16
# contraction, which biases avg(l_discount) by 8.9e-5 (the eleven discount
# values 0.00-0.10 round to bf16 the same way every time).
RTOL = 2e-5
SF = 1                                 # TPC-H scale factor: 6,001,215 rows
DB_DIR = os.path.join(ROOT, ".smoke_db")
GROUP_COMMIT = 64


class CompileClock:
    """Sums JAX's backend-compile durations (any thread) so each query can
    report the compile seconds it paid."""

    def __init__(self):
        import jax
        self.total = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if "backend_compile" in event:
            with self._lock:
                self.total += duration


def device_info() -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {info['platform']!r}")
    return info


def load(seed: int):
    shutil.rmtree(DB_DIR, ignore_errors=True)
    t0 = time.perf_counter()
    cols = tpch.lineitem(SF, seed)
    db = Database(durable=DB_DIR, group_commit=GROUP_COMMIT)
    h = db.create_table("lineitem", tpch.SCHEMA)
    h.bulk_insert(cols)
    base = h.store.baseline
    print(f"[load] rows={base.nrows} blocks={base.n_blocks} "
          f"block_rows={base.block_rows} seconds={time.perf_counter() - t0}",
          flush=True)
    return db, h, cols


def reference(h, q):
    needed = sorted(VectorEngine.columns_needed(q, h.store.schema.names))
    tbl, _ = h.store.scan(columns=needed)
    return VectorEngine().execute(tbl, q)


def compare(name: str, q, rows, ref) -> float:
    """Keys and counts exactly, float aggregates within RTOL; returns the
    largest relative error."""
    keys = [tuple(r[k] for k in q.group_by) for r in rows]
    want = [tuple(r[k] for k in q.group_by) for r in ref]
    if keys != want:
        raise AssertionError(f"{name}: groups {keys} != reference {want}")
    worst = 0.0
    for r, w in zip(rows, ref):
        for a in q.aggs:
            got, exp = r[a.alias], w[a.alias]
            if a.op == "count":
                if got != exp:
                    raise AssertionError(
                        f"{name}: {a.alias} {got} != {exp} for {keys}")
                continue
            err = abs(got - exp) / max(abs(exp), 1e-30)
            worst = max(worst, err)
            if err > RTOL:
                raise AssertionError(f"{name}: {a.alias} {got} vs {exp} "
                                     f"(rel err {err} > {RTOL})")
    return worst


def serve(srv, h, clock, queries, **hints) -> float:
    """Send each query through the server and hold every answer to the
    device path and to the reference."""
    worst = 0.0
    base = h.store.baseline
    for name, q in queries:
        c0 = clock.total
        t = srv.submit(q, "lineitem", device_route="collective", **hints)
        rs = t.result(timeout=1200)
        compile_s = clock.total - c0
        st, plan = rs.stats, rs.plan
        if not st.used_device:
            raise AssertionError(f"{name}: answered on the host ({plan})")
        if plan.degraded or plan.repaired:
            raise AssertionError(f"{name}: degraded={plan.degraded} "
                                 f"repaired={plan.repaired}")
        if q.limit is not None and not st.topk_pushdown:
            raise AssertionError(f"{name}: the top-k slice did not run")
        dp = pushdown.plan_device(h.store, q)
        planes = 1 + len(dp.group_cols) + max(len(dp.value_cols), 1)
        staged = base.n_blocks * base.block_rows * planes * 4
        err = compare(name, q, rs.rows, reference(h, q))
        worst = max(worst, err)
        print(f"[query] {name}: used_device={st.used_device} "
              f"route={st.device_route} n_devices={st.n_devices} "
              f"n_shards={st.n_shards} degraded={plan.degraded} "
              f"repaired={plan.repaired} groups={len(rs.rows)} "
              f"parity=ok max_rel_err={err} "
              f"shape(Nb,Bk,tile)=({base.n_blocks},{base.block_rows},"
              f"{st.device_tile_blocks}) staged_bytes={staged} "
              f"wall_s={t.latency_s} compile_s={compile_s}", flush=True)
    return worst


def write_batch(db, h, cols, seed: int):
    """Inserts of new orders' lines, updates of l_quantity/l_discount and
    deletes, all through the logged DML; returns what to read back."""
    rng = np.random.default_rng(seed + 1)
    n_orders = SF * tpch.ORDERS_PER_SF
    new = tpch.lineitem(500 / tpch.ORDERS_PER_SF, seed + 2,
                        first_order=n_orders)
    names = tpch.SCHEMA.names
    inserted = [{c: new[c][i].item() for c in names}
                for i in range(len(new["l_pk"]))]
    pks = rng.choice(cols["l_pk"], 2000, replace=False)
    upd_pks, del_pks = pks[:1000], pks[1000:]
    updates = {int(pk): {"l_quantity": float(rng.integers(1, 51)),
                         "l_discount": float(rng.integers(0, 11)) / 100}
               for pk in upd_pks}
    t0 = time.perf_counter()
    for row in inserted:
        h.insert(row)
    for pk, ch in updates.items():
        h.update(pk, ch)
    for pk in del_pks:
        h.delete(int(pk))
    db.flush_wal()
    t1 = time.perf_counter()
    h.major_compact()
    print(f"[write] inserts={len(inserted)} updates={len(updates)} "
          f"deletes={len(del_pks)} dml_s={t1 - t0} "
          f"compact_s={time.perf_counter() - t1} "
          f"rows={h.store.baseline.nrows}", flush=True)
    return inserted, updates, del_pks


def read_back(h, n0: int, inserted, updates, del_pks) -> None:
    want = n0 + len(inserted) - len(del_pks)
    if h.store.baseline.nrows != want:
        raise AssertionError(f"rows {h.store.baseline.nrows} != {want}")
    for row in inserted:
        if h.get(row["l_pk"]) != row:
            raise AssertionError(f"insert of {row['l_pk']} not read back")
    for pk, ch in updates.items():
        got = h.get(pk)
        if any(got[c] != v for c, v in ch.items()):
            raise AssertionError(f"update of {pk} not read back: {got}")
    for pk in del_pks:
        if h.get(int(pk)) is not None:
            raise AssertionError(f"delete of {pk} not read back")
    print(f"[write] read back {len(inserted)} inserts, {len(updates)} "
          f"updates, {len(del_pks)} deletes", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    info = device_info()
    print(f"[cache] {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    db, h, cols = load(args.seed)
    print(f"[query] Q1 expression aggregates left out (not expressible in "
          f"QAgg): {', '.join(tpch.Q1_LEFT_OUT)}", flush=True)
    srv = QueryServer(db, workers=1)
    try:
        if args.chips == 4:
            if info["count"] != 4:
                raise SystemExit(f"--chips 4 needs 4 devices, JAX sees "
                                 f"{info['count']}")
            t = srv.submit(tpch.q1(), "lineitem", n_shards=4,
                           device_route="collective")
            rs = t.result(timeout=1200)
            st = rs.stats
            if (st.n_devices != 4 or st.device_route != "collective"
                    or not st.used_device or rs.plan.degraded):
                raise AssertionError(
                    f"q1 on 4 chips: n_devices={st.n_devices} "
                    f"route={st.device_route} used_device={st.used_device} "
                    f"degraded={rs.plan.degraded}")
            err = compare("q1", tpch.q1(), rs.rows,
                          reference(h, tpch.q1()))
            print(f"[chips4] q1 n_devices={st.n_devices} "
                  f"route={st.device_route} n_shards={st.n_shards} "
                  f"degraded={rs.plan.degraded} parity=ok "
                  f"max_rel_err={err} wall_s={t.latency_s} "
                  f"compile_s={clock.total}", flush=True)
        else:
            queries = [("q1", tpch.q1()),
                       ("q1_topk", tpch.q1(limit=2)),
                       ("shipmode_1994", tpch.shipmode_year(1994))]
            worst = serve(srv, h, clock, queries)
            n0 = h.store.baseline.nrows
            wrote = write_batch(db, h, cols, args.seed)
            read_back(h, n0, *wrote)
            worst = max(worst, serve(srv, h, clock, queries))
            print(f"[done] phases passed: device, load, serve, write, "
                  f"serve-after-write; max_rel_err={worst} (rtol {RTOL}); "
                  f"compile_s_total={clock.total}", flush=True)
    finally:
        srv.close()
        shutil.rmtree(DB_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
