"""The partitioned deployment through the served path: one TPC-H LINEITEM
on a 4-device 'scan' mesh, Q1 and ship-mode-year queries at several
selectivities through ``QueryServer.submit(device_route="collective")``.

Every device answer runs on all four devices over a block→chip placement
fixed per baseline generation, and matches a plain numpy reference (keys
and counts exact, float aggregates within 2e-5 relative error), before and
after an RF1/RF2 pair and a major compaction.  On one device the shards
and launch shapes are the planner's range partition, as before placement.
"""
import json

import numpy as np
import pytest

from bench import tpch
from repro.core import partition
from repro.core.engine import QAgg, Query
from repro.core.placement import range_partition
from repro.core.relation import Predicate, PredOp
from repro.core.session import Database

from tests.test_distributed import run_py

SF = 0.01
REL_ERR = 2e-5              # the configurations' float-aggregate guarantee


def q1(hi):
    return Query(preds=(Predicate("l_shipdate", PredOp.LE, hi),),
                 group_by=("l_returnflag", "l_linestatus"),
                 aggs=(QAgg("sum", "l_quantity", "sum_qty"),
                       QAgg("sum", "l_extendedprice", "sum_base_price"),
                       QAgg("avg", "l_quantity", "avg_qty"),
                       QAgg("avg", "l_extendedprice", "avg_price"),
                       QAgg("avg", "l_discount", "avg_disc"),
                       QAgg("count", None, "count_order")),
                 sort_by=("l_returnflag", "l_linestatus"))


def shipmode(lo, width=364):
    return Query(preds=(Predicate("l_shipdate", PredOp.BETWEEN, lo,
                                  lo + width),),
                 group_by=("l_shipmode",),
                 aggs=(QAgg("count", None, "n"),
                       QAgg("sum", "l_quantity", "sum_qty"),
                       QAgg("avg", "l_extendedprice", "avg_price")),
                 sort_by=("l_shipmode",))


# widest first, down to a week of ship dates
QUERIES = [q1(tpch.day("1998-12-01") - 90), shipmode(tpch.day("1993-01-01")),
           q1(tpch.day("1993-06-01")), shipmode(tpch.day("1996-03-01"), 6)]


def reference(cols, q):
    """Grouped count/sum/avg over ``cols`` in float64, keys sorted."""
    m = np.ones(cols["l_pk"].shape[0], bool)
    for p in q.preds:
        x = cols[p.column]
        m &= (x <= p.value) if p.op == PredOp.LE else \
            (x >= p.value) & (x <= p.value2)
    keys = np.rec.fromarrays([cols[g][m] for g in q.group_by])
    uniq, inv = np.unique(keys, return_inverse=True)
    cnt = np.bincount(inv, minlength=len(uniq))
    rows = []
    for i, u in enumerate(uniq):
        r = {g: bytes(u[j]) for j, g in enumerate(q.group_by)}
        for a in q.aggs:
            if a.op == "count":
                r[a.alias] = int(cnt[i])
                continue
            s = np.bincount(inv, weights=cols[a.column][m].astype(np.float64),
                            minlength=len(uniq))[i]
            r[a.alias] = s / cnt[i] if a.op == "avg" else s
        rows.append(r)
    return rows


def compare(q, got, want):
    """(keys and counts equal, largest relative float error)."""
    def key(r):
        return tuple(bytes(r[g]) if not isinstance(r[g], str)
                     else r[g].encode() for g in q.group_by)
    if [key(r) for r in got] != [key(r) for r in want]:
        return False, 0.0
    worst = 0.0
    for g, w in zip(got, want):
        for a in q.aggs:
            if a.op == "count":
                if int(g[a.alias]) != w[a.alias]:
                    return False, worst
            else:
                worst = max(worst, abs(float(g[a.alias]) - w[a.alias])
                            / max(abs(w[a.alias]), 1e-30))
    return True, worst


# run in a subprocess that sees four CPU devices
SERVED = '''
import json, sys
sys.path.insert(0, ".")
import numpy as np
from bench import tpch
from repro.core import partition, spans
from tests.test_collective_served import QUERIES, SF, compare, reference
from repro.core.serving import QueryServer
from repro.core.session import Database

launches = []
stack = partition.stack_device_stage


def keep(stage, shards, block_mask, mesh, tile=1):
    launches.append(([[s.lo_block, s.hi_block] for s in shards],
                     int(mesh.devices.size)))
    return stack(stage, shards, block_mask, mesh, tile)


partition.stack_device_stage = keep
cols = tpch.lineitem(SF, [7, 100])
db = Database(durable=WORKDIR, group_commit=64)
h = db.create_table("lineitem", tpch.SCHEMA, block_rows=1024)
h.bulk_insert(cols)
spans.enable(True)
out = {"answers": [], "n_blocks": []}


def serve(phase, **hints):
    with QueryServer(db, workers=2) as srv:
        tickets = [srv.submit(q, "lineitem", device_route="collective",
                              **hints) for q in QUERIES]
        for qi, t in enumerate(tickets):
            rs = t.result(timeout=600)
            st = rs.stats
            ok, err = compare(QUERIES[qi], rs.rows,
                              reference(cols, QUERIES[qi]))
            out["answers"].append({
                "phase": phase, "query": qi, "ok": ok, "err": err,
                "used_device": st.used_device, "n_devices": st.n_devices,
                "n_shards": st.n_shards, "degraded": list(st.degraded)})
    out["n_blocks"].append(h.store.baseline.n_blocks)


serve("load")
serve("load", n_shards=6)       # a wider fan-out than the planner's
# RF1: new orders after the last one; RF2: every line of a few orders
base_orders = int(round(SF * tpch.ORDERS_PER_SF))
rf1 = tpch.lineitem(15 / tpch.ORDERS_PER_SF, [7, 4, 0],
                    first_order=base_orders)
for i in range(rf1["l_pk"].shape[0]):
    h.insert({c: rf1[c][i].item() for c in tpch.SCHEMA.names})
gone = np.isin(cols["l_orderkey"], tpch.order_key(np.arange(0, 150, 10)))
for pk in cols["l_pk"][gone]:
    h.delete(int(pk))
db.flush_wal()
cols = {c: np.concatenate([cols[c][~gone], rf1[c]]) for c in cols}
serve("refreshed")
h.major_compact()
serve("compacted")
got, _ = spans.drain()
out["place_spans"] = sum(s.name == "ob.place" for s in got)
out["launches"] = launches
print("RESULT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The served runs on a 4-device CPU mesh, as one subprocess."""
    workdir = str(tmp_path_factory.mktemp("x4-db"))
    body = f"WORKDIR = {workdir!r}\n" + SERVED
    line = [ln for ln in run_py(body, ndev=4).splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def chip_of_block(launch, n_blocks):
    """The chip each baseline block ran on in one collective launch: shard
    i of S on chip i * D // S."""
    ranges, d = launch
    chips = [None] * n_blocks
    for i, (lo, hi) in enumerate(ranges):
        for b in range(lo, hi):
            chips[b] = i * d // len(ranges)
    return chips


def test_every_device_answer_runs_on_all_four_chips(served):
    device = [a for a in served["answers"] if a["phase"] != "refreshed"]
    assert len(device) == 12
    for a in device:
        assert a["used_device"] and a["n_devices"] == 4, a
        assert a["degraded"] == [] and a["n_shards"] % 4 == 0, a
    # a wider fan-out cuts each chip's partition, it adds no chip
    assert {a["n_shards"] for a in device} >= {4, 8}


def test_served_answers_match_the_numpy_reference(served):
    for a in served["answers"]:
        assert a["ok"], a
        assert a["err"] <= REL_ERR, a


def test_placement_is_fixed_per_baseline_generation(served):
    first_gen, second_gen = served["n_blocks"][0], served["n_blocks"][-1]
    launches = served["launches"]
    assert len(launches) == 12 and all(d == 4 for _, d in launches)
    before = [chip_of_block(l, first_gen) for l in launches[:8]]
    after = [chip_of_block(l, second_gen) for l in launches[8:]]
    # one block->chip map for every selectivity and fan-out of a generation
    assert all(m == before[0] for m in before)
    assert all(m == after[0] for m in after)
    for m in (before[0], after[0]):
        assert None not in m and sorted(set(m)) == [0, 1, 2, 3]
        assert m == sorted(m)            # chip partitions are pk ranges
    # computed once for the load and once for the compacted baseline
    assert served["place_spans"] == 2


def test_writes_then_compaction_keep_answers_exact(served):
    refreshed = [a for a in served["answers"] if a["phase"] == "refreshed"]
    compacted = [a for a in served["answers"] if a["phase"] == "compacted"]
    assert len(refreshed) == len(compacted) == 4
    # pending writes scan on the host; the compacted baseline on the chips
    assert not any(a["used_device"] for a in refreshed)
    assert all(a["ok"] and a["used_device"] and a["n_devices"] == 4
               for a in compacted)


@pytest.mark.parametrize("n_shards", [1, 3, 8])
def test_one_device_shards_and_launch_shapes_are_the_planners(
        monkeypatch, n_shards):
    """On one device an answer's shards are ``range_partition(base, n)``'s
    non-empty ranges, stacked as [n, widest shard, ...], with no chip
    placement computed."""
    cols = tpch.lineitem(0.002, [3, 100])
    db = Database()
    h = db.create_table("lineitem", tpch.SCHEMA, block_rows=1024)
    h.bulk_insert(cols)
    base = h.store.baseline
    stacked = []
    stack = partition.stack_device_stage

    def keep(stage, shards, block_mask, mesh, tile=1):
        out = stack(stage, shards, block_mask, mesh, tile)
        stacked.append((list(shards), out[0][0].shape))
        return out

    monkeypatch.setattr(partition, "stack_device_stage", keep)
    for q in QUERIES[:2]:
        rs = db.query(q, n_shards=n_shards, device_route="collective")
        assert rs.stats.used_device and rs.stats.n_devices == 1
        assert rs.stats.n_shards == n_shards
        assert rs.stats.collective_bytes == 0
        ok, err = compare(q, rs.rows, reference(cols, q))
        assert ok and err <= REL_ERR
    want = [s for s in range_partition(base, n_shards) if s.n_blocks]
    for shards, shape in stacked:
        assert shards == want
        assert shape == (len(want), max(s.n_blocks for s in want),
                         base.block_rows)
    assert not hasattr(base, "_chip_partitions")


HOST_ROUTE = '''
import json, sys
sys.path.insert(0, ".")
import numpy as np
from repro.core import partition
from repro.core.engine import VectorEngine
from repro.core.partition import ShardedScanExecutor
from tests.test_pushdown import make_store
from tests.test_serving import GROUPED_Q

placed = []
launch = partition.launch_shard_kernels


def keep(plan, stage, shards, block_mask, devices, *a, **k):
    placed.append([[s.lo_block, s.hi_block, devices[s.shard_id].id]
                   for s in shards])
    return launch(plan, stage, shards, block_mask, devices, *a, **k)


partition.launch_shard_kernels = keep
store = make_store(np.random.default_rng(13), n=512, block_rows=32,
                   dml=False)
want = {r["g"]: r for r in VectorEngine().execute(store.scan()[0],
                                                  GROUPED_Q)}
out = []
for shards in (2, 24):          # 24: more shards than a chip has blocks
    rows, st = ShardedScanExecutor(n_shards=shards, device=True,
                                   device_route="host").execute_stats(
        store, GROUPED_Q)
    got = {r["g"]: r for r in rows}
    ok = got.keys() == want.keys() and all(
        got[g]["n"] == want[g]["n"] and np.isclose(got[g]["sv"], want[g]["sv"],
                                                   rtol=1e-4, atol=1e-3)
        for g in want)
    out.append({"ok": bool(ok), "n_devices": st.n_devices,
                "route": st.device_route, "degraded": st.degraded,
                "placed": placed.pop()})
print("RESULT " + json.dumps(out))
'''


def test_host_route_on_four_chips_keeps_each_block_on_its_chip():
    """The per-shard-launch route runs each shard on its block's chip,
    the collective's placement, and launches no empty shard."""
    line = [ln for ln in run_py(HOST_ROUTE, ndev=4).splitlines()
            if ln.startswith("RESULT ")][-1]
    runs = json.loads(line[len("RESULT "):])
    chip_of = []
    for r in runs:
        assert r["ok"] and r["n_devices"] == 4 and r["route"] == "host", r
        assert r["degraded"] == [] and all(hi > lo for lo, hi, _ in
                                           r["placed"])
        chips = {b: dev for lo, hi, dev in r["placed"]
                 for b in range(lo, hi)}
        assert sorted(chips) == list(range(16))
        chip_of.append([chips[b] for b in range(16)])
    # 16 blocks: four a chip, whatever the fan-out
    assert chip_of[0] == chip_of[1] == [b // 4 for b in range(16)]
