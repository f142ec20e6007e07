"""The paced write stream through the table's logged DML, its read-back,
and the reference at the snapshot a query read."""
import time

import numpy as np

from bench import deploy, reference, traffic, window
from bench.run import lost_writes
from bench.tests.fixture import tiny_config


def test_refresh_stream_is_read_back_and_seen_at_each_snapshot():
    cfg = tiny_config("w", ["t0"], sf=0.002)
    dep = deploy.build(cfg, 21, keep_keys=["t0"])
    try:
        t = {"writes": {"tenant": "t0", "first_s": 0.0, "period_s": 0.05,
                        "functions": ["rf1", "rf2"], "orders_per_sf": 10000}}
        plan = traffic.plan_refreshes(t, cfg["tables"][0], dep.keys["t0"],
                                      21, 0.1)
        assert [r.kind for r in plan] == ["rf1", "rf2"]
        applied = []
        window._writer(dep, plan, time.monotonic(), applied)
        assert [a.error for a in applied] == [None, None]
        assert len(applied[0].inserts) == len(plan[0].rows) > 0
        assert len(applied[1].deletes) == len(plan[1].pks) > 0
        assert lost_writes(applied, dep.handles) == 0

        ref = reference.RefTable(deploy.generate(cfg["tables"][0], 21, 0))
        for a in applied:
            ref.inserts += a.inserts
            ref.deletes += a.deletes
        q = traffic.RefQuery("l_shipdate", None, 20000, ("l_shipmode",),
                             (("count", None, "n"),
                              ("sum", "l_quantity", "q")), ("l_shipmode",))
        h = dep.handles["t0"]
        base_n = sum(r["n"] for r in ref.answer(q, 0))
        mid = applied[0].inserts[-1][0]            # after RF1, before RF2
        want_mid = ref.answer(q, mid)
        assert sum(r["n"] for r in want_mid) == \
            base_n + sum(r["l_shipdate"] <= 20000 for r in plan[0].rows)
        # the program answers at its own snapshot: after both functions
        rs = h.query(traffic.Item(0, "x", "t0", "t0", q, None).query())
        diff, err = reference.compare(q, rs.rows, ref.answer(q, rs.plan.ts))
        assert diff is None and err < 1e-9

        # a lost write is seen: delete one inserted row behind the stream
        h.delete(applied[0].inserts[0][1]["l_pk"])
        assert lost_writes(applied, dep.handles) == 1
        assert np.all(np.diff([ts for ts, _ in applied[0].inserts]) > 0)
    finally:
        dep.close()
