"""The reference's cube answers equal its direct answers: on random ranges
of both ad-hoc classes, at the ends of the table's range, and at snapshots
that hold inserts and deletes; and every ad-hoc query takes the cube."""
import json
import os

import numpy as np
import pytest

from bench import deploy, reference, spec, tpch, traffic, window
from bench.run import check
from bench.tests import fixture

SF = 0.002                              # 12,002 rows
SEED = 2**31 + 29


def _mix():
    with open(os.path.join(fixture.BENCH, "queries", "adhoc.json")) as f:
        return {c["name"]: c for c in json.load(f)["classes"]}


def _query(cls, lo, hi):
    return traffic.RefQuery(cls["pred"]["column"], lo, hi,
                            tuple(cls["group_by"]),
                            tuple(tuple(a) for a in cls["aggs"]),
                            tuple(cls["sort_by"]))


def _table():
    return reference.RefTable(tpch.lineitem(SF, [SEED, 100]))


def _same(q, got, want):
    """Group keys identical, counts exact, float aggregates within 1e-12
    relative."""
    assert [tuple(r[k] for k in q.group_by) for r in got] == \
        [tuple(r[k] for k in q.group_by) for r in want]
    for g, w in zip(got, want):
        for op, _, alias in q.aggs:
            if op == "count":
                assert g[alias] == w[alias]
            else:
                assert g[alias] == pytest.approx(w[alias], rel=1e-12, abs=0)


def _cube_is_direct(ref, q, ts=None):
    got = ref.from_cube(q, ts)
    assert got is not None
    _same(q, got, ref.direct(q, ts))
    return got


@pytest.mark.parametrize("name", ["q1", "shipmode_year"])
def test_cube_equals_direct_on_random_ranges(name):
    cls, ref = _mix()[name], _table()
    ship = ref.cols["l_shipdate"]
    lo, hi = int(ship.min()), int(ship.max())
    rng = np.random.default_rng([SEED, 7])
    for _ in range(120):
        a = int(rng.integers(lo - 60, hi + 60))
        if cls["pred"]["op"] == "le":
            q = _query(cls, None, a)
        else:
            q = _query(cls, a, a + int(rng.integers(-5, 800)))
        _cube_is_direct(ref, q)


@pytest.mark.parametrize("case", ["below_min", "above_max", "empty",
                                  "one_day", "whole", "open_low"])
@pytest.mark.parametrize("name", ["q1", "shipmode_year"])
def test_cube_equals_direct_at_range_ends(name, case):
    cls, ref = _mix()[name], _table()
    ship = ref.cols["l_shipdate"]
    lo, hi = int(ship.min()), int(ship.max())
    mid = int(np.median(ship))
    bounds = {"below_min": (lo - 500, lo + 40), "above_max": (hi - 40,
                                                              hi + 500),
              "empty": (mid + 1, mid), "one_day": (mid, mid),
              "whole": (lo - 1, hi + 1), "open_low": (None, mid)}[case]
    q = _query(cls, *bounds)
    rows = _cube_is_direct(ref, q)
    assert (rows == []) == (case == "empty")
    if case == "one_day":
        n = [alias for op, _, alias in q.aggs if op == "count"][0]
        assert sum(r[n] for r in rows) == int((ship == mid).sum())


def _writes(ref, mode=b"BOAT"):
    """An RF1-like batch of inserted rows at timestamps 1.., one of them
    with ship mode ``mode``; then deletes of baseline rows and of inserted
    ones, that odd row among them.  Returns the odd row and the snapshots
    that insert and delete it."""
    add = tpch.lineitem(0.0004, [SEED, 4],
                        first_order=int(SF * tpch.ORDERS_PER_SF))
    rows = [{c: add[c][i].item() for c in tpch.SCHEMA.names}
            for i in range(len(add["l_pk"]))]
    odd = rows[len(rows) // 2]
    odd["l_shipmode"] = mode
    ref.inserts += [(1 + i, r) for i, r in enumerate(rows)]
    t = len(rows) + 1
    base = ref.cols["l_pk"][::97]
    ref.deletes += [(t + i, int(pk)) for i, pk in enumerate(base)]
    t += len(base)
    gone = [r["l_pk"] for r in rows[::5]] + [odd["l_pk"]]
    ref.deletes += [(t + i, pk) for i, pk in enumerate(gone)]
    ts_odd = rows.index(odd) + 1
    return odd, ts_odd, t + gone.index(odd["l_pk"])


@pytest.mark.parametrize("name", ["q1", "shipmode_year"])
def test_cube_equals_direct_at_write_snapshots(name):
    cls, ref = _mix()[name], _table()
    odd, ts_odd, ts_odd_gone = _writes(ref)
    day = odd["l_shipdate"]
    wide = _query(cls, None, day) if cls["pred"]["op"] == "le" \
        else _query(cls, day - 200, day + 164)
    narrow = _query(cls, None, day - 1) if cls["pred"]["op"] == "le" \
        else _query(cls, day + 1, day + 365)
    last = ref.deletes[-1][0]
    snaps = sorted({0, ts_odd - 1, ts_odd, ts_odd + 3, len(ref.inserts),
                    len(ref.inserts) + 40, ts_odd_gone - 1, ts_odd_gone,
                    last}) + [None]
    # the baseline lacks the odd row's ship mode: the ship-mode class goes
    # the direct way while the row is visible and in range
    lacks = name == "shipmode_year"
    for ts in snaps:
        visible = ts is not None and ts_odd <= ts < ts_odd_gone
        for q in (wide, narrow):
            got = ref.from_cube(q, ts)
            if lacks and visible and q is wide:
                assert got is None
                assert b"BOAT" in [r["l_shipmode"] for r in ref.answer(q, ts)]
            else:
                _cube_is_direct(ref, q, ts)
                _same(q, ref.answer(q, ts), ref.direct(q, ts))
    # the writes move the answer: the deletes are seen
    assert ref.answer(wide, 0) != ref.answer(wide, None)


@pytest.mark.parametrize("workload", ["sf1-adhoc-c4", "mt4-tput4-rf"])
def test_every_adhoc_query_takes_the_cube(workload):
    cell = spec.load_cell(fixture.ROOT, workload)
    tenants = {t["tenant"]: t["name"] for t in cell.config["tables"]}
    sched = traffic.Schedule(cell.traffic, cell.queries, tenants, SEED,
                             cell.run_seconds)
    ref = _table()
    _writes(ref, mode=b"MAIL")         # RF1 draws from the spec's modes
    for i in sched.first(200):
        q = sched.item(i).ref
        for ts in (0, len(ref.inserts) // 2, None):
            assert ref.from_cube(q, ts) is not None, (q, ts)


def test_check_compares_every_answer_through_the_cube(tmp_path):
    root = fixture.make_root(str(tmp_path))
    cell = spec.load_cell(root, "tiny-rf")
    seed, seconds = SEED, 3.0
    t1 = cell.config["tables"][1]
    base = deploy.generate(t1, seed, 1)
    plan = traffic.plan_refreshes(cell.traffic, t1, base, seed, seconds)
    applied, ts = [], 1
    for rf in plan:
        ins = [(ts + i, r) for i, r in enumerate(rf.rows)]
        ts += len(ins)
        dels = [(ts + i, pk) for i, pk in enumerate(rf.pks)]
        ts += len(dels)
        applied.append(window.Applied(rf.kind, rf.table, 0.0, 0.0, ins, dels))
    assert ts > 100
    tenants = {t["tenant"]: t["name"] for t in cell.config["tables"]}
    sched = traffic.Schedule(cell.traffic, cell.queries, tenants, seed,
                             seconds)
    refs = {t["name"]: reference.RefTable(deploy.generate(t, seed, k))
            for k, t in enumerate(cell.config["tables"])}
    for a in applied:
        refs[a.table].inserts += a.inserts
        refs[a.table].deletes += a.deletes
    answers = []
    for n, i in enumerate(sched.first(len(sched))):
        item = sched.item(i)
        at = ts * n // len(sched)
        answers.append((item, refs[item.table].direct(item.ref, at), at))
    lines = []
    numbers, _ = check(cell, seed, answers, applied, 0, lines.append)
    assert numbers["wrong_answers"] == 0
    assert numbers["max_rel_err"] < 1e-12
    assert reference.verdict(numbers)
    last = dict(kv.split("=") for kv in lines[-1].split()[1:])
    assert int(last["answers"]) == len(answers)
    assert int(last["cube"]) == int(last["distinct"]) > 1
