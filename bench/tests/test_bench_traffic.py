"""The generators: TPC-H row counts, determinism from the seed, and the
same amount of work for every seed."""
import json
import os

import numpy as np
import pytest

from bench import traffic, tpch
from bench.tests.fixture import BENCH


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("sf,rows", [(1, 6_001_215), (0.25, 1_500_304)])
def test_tpch_row_counts(sf, rows):
    per = tpch.lines_per_order(sf, np.random.default_rng(0))
    assert per.sum() == rows
    assert per.shape[0] == round(sf * tpch.ORDERS_PER_SF)
    assert per.min() >= 1 and per.max() <= 7


def test_lineitem_is_a_function_of_the_seed():
    a, b = tpch.lineitem(0.001, [2**31 + 5, 100]), \
        tpch.lineitem(0.001, [2**31 + 5, 100])
    c = tpch.lineitem(0.001, [2**31 + 6, 100])
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["l_quantity"], c["l_quantity"])
    assert np.all(np.diff(a["l_pk"]) > 0)


OPEN = {"loop": "open", "rate_qps": 1.5,
        "tenants": {"t0": 12, "t1": 6, "t2": 4, "t3": 3}}


def _sched(name, seed, seconds=51.0, **over):
    t = dict(_load("traffic", name), **over)
    tables = {ten: ten for ten in t["tenants" if t["loop"] == "open"
                                    else "streams"]}
    return traffic.Schedule(t, _load("queries", t["queries"]), tables,
                            seed, seconds)


def test_schedules_repeat_for_a_seed():
    a, b = _sched("tput4-rf", 2**33, **OPEN), \
        _sched("tput4-rf", 2**33, **OPEN)
    assert [a.item(i) for i in range(len(a))] == \
        [b.item(i) for i in range(len(b))]


def test_open_loop_work_is_the_same_for_every_seed():
    a, b = _sched("tput4-rf", 1, **OPEN), _sched("tput4-rf", 2, **OPEN)
    assert len(a) == len(b) == round(OPEN["rate_qps"] * 51.0)
    # the same gaps and the same class and tenant counts, in other orders
    g1 = traffic.exp_gaps(100, 2.0, np.random.default_rng(1))
    g2 = traffic.exp_gaps(100, 2.0, np.random.default_rng(2))
    assert np.array_equal(np.sort(g1), np.sort(g2))
    assert not np.array_equal(g1, g2)
    assert np.mean(g1) == pytest.approx(0.5, rel=0.05)
    for s in (a, b):
        assert s.at[0] == 0.0 and s.at[-1] < 51.0
    assert np.array_equal(np.bincount(a.cls_idx), np.bincount(b.cls_idx))
    assert np.array_equal(np.bincount(a.ten_idx), np.bincount(b.ten_idx))
    assert not np.array_equal(a.cls_idx, b.cls_idx)


def test_closed_loop_blocks_hold_the_mix_exactly():
    # a 1:3 mix: every block of 4 of a tenant's queue holds one q1, so any
    # prefix of the queue, whichever of its streams sends it, holds the mix
    # to within one query
    s = _sched("adhoc-c4", 9)
    assert [m for _, m in s.queues] == [4]
    blocks = s.cls_idx.reshape(-1, 4)
    assert (blocks == 0).sum(axis=1).tolist() == [1] * blocks.shape[0]
    assert not np.array_equal(s.cls_idx, _sched("adhoc-c4", 10).cls_idx)
    # the throughput test's shape: each tenant owns one stream
    t = _sched("tput4-rf", 9)
    assert [m for _, m in t.queues] == [1, 1, 1, 1]
    for k, (idx, _) in enumerate(t.queues):
        assert {t.item(i).tenant for i in idx[:40]} == {f"t{k}"}
        blocks = t.cls_idx[idx].reshape(-1, 4)
        assert (blocks == 0).sum(axis=1).tolist() == [1] * blocks.shape[0]
    with pytest.raises(ValueError):
        traffic.Schedule(_load("traffic", "adhoc-c4"),
                         {"classes": [{"name": "x", "share": 0.5}]},
                         {"default": "default"}, 1, 1.0)


@pytest.mark.parametrize("name", ["adhoc-c4", "tput4-rf"])
def test_drawn_constants_do_not_repeat(name):
    # a tenant's constants of a class never repeat while the range lasts
    # (Q1's DELTA has 61 days)
    s = _sched(name, 4)
    seen = {}
    for i in s.first(4 * 60):
        it = s.item(i)
        seen.setdefault((it.tenant, it.cls), []).append((it.ref.lo,
                                                         it.ref.hi))
    for key, consts in seen.items():
        assert len(set(consts)) == len(consts), key


def test_query_constants_follow_the_mix():
    s = _sched("adhoc-c4", 4)
    lo_q1, hi_q1 = tpch.day("1998-12-01") - 120, tpch.day("1998-12-01") - 60
    for i in s.first(200):
        r = s.item(i).ref
        if s.item(i).cls == "q1":
            assert r.lo is None and lo_q1 <= r.hi <= hi_q1
        else:
            assert r.hi - r.lo == 364
            assert tpch.day("1993-01-01") <= r.lo <= tpch.day("1997-12-31")


def test_refresh_plan_sizes():
    t = _load("traffic", "tput4-rf")
    table = {"name": "t1", "tenant": "t1", "scale_factor": 0.01}
    cols = tpch.lineitem(0.01, [5, 101])
    plan = traffic.plan_refreshes(t, table, cols, 5, 51.0)
    assert [r.kind for r in plan] == ["rf1", "rf2", "rf1", "rf2", "rf1"]
    assert [r.at_s for r in plan] == [5.0, 15.0, 25.0, 35.0, 45.0]
    n_orders = round(0.01 * 1500)
    rf1 = plan[0].rows
    assert len({r["l_orderkey"] for r in rf1}) == n_orders
    assert min(r["l_pk"] for r in rf1) > cols["l_pk"].max()
    gone = plan[1].pks + plan[3].pks
    assert len(set(gone)) == len(gone)
    assert set(gone) <= set(cols["l_pk"].tolist())
    assert len({pk // 8 for pk in plan[1].pks}) == n_orders   # orderkeys
