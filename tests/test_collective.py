"""Single-launch collective device fan-out + limit-aware top-k pushdown.

The contracts under test:

* ``ops.sharded_scan_agg`` (one shard_map launch, psum/pmin/pmax
  tree-reduce on the 'scan' mesh) matches the pure-jnp oracle and the
  per-shard-launch host-merge route bit-for-bit on counts and to f32
  tolerance on sums — including the on-device top-k accumulator slice.
* ``ShardedScanExecutor(device=True)`` returns VectorEngine's answer on
  either device route, across 1/2/4 shards, and falls back to the host
  path for merge-on-read DML and NULL-bearing columns.
* Limit pushdown (per-shard partial heaps, heap merges, projection row
  top-k) is answer-identical to full-merge-then-sort, with ties broken
  deterministically, and never fires for non-pushable sorts (aggregate
  aliases).
"""
import numpy as np
import pytest

from repro.core.engine import QAgg, Query, VectorEngine
from repro.core.lsm import LSMStore
from repro.core.partition import (GroupedPartial, ShardedScanExecutor,
                                  topk_group_limit, tree_reduce)
from repro.core.pushdown import PushdownExecutor
from repro.core.relation import ColType, Predicate, PredOp, schema

from tests.test_pushdown import make_store, make_null_store, norm


# ---------------------------------------------------------------------------
# kernel-level: shard_map collective vs oracle vs host merge
# ---------------------------------------------------------------------------


def _stacked_inputs(rng, S=4, Nb=3, Bk=128, K=2, V=2, ndv=(5, 3)):
    deltas = rng.integers(0, 60, (S, Nb, Bk)).astype(np.int32)
    bases = rng.integers(0, 20, (S, Nb)).astype(np.int32)
    counts = np.full((S, Nb), Bk, np.int32)
    counts[-1, -1] = Bk // 2                     # a partial block
    codes = np.stack([rng.integers(0, d, (S, Nb, Bk))
                      for d in ndv], axis=2).astype(np.int32)
    values = rng.normal(size=(S, Nb, V, Bk)).astype(np.float32)
    mask = np.ones((S, Nb), bool)
    mask[0, 1] = False                           # a pruned block
    return deltas, bases, counts, codes, values, mask


@pytest.mark.device
@pytest.mark.parametrize("topk", [0, 4])
def test_sharded_scan_agg_matches_ref(rng, topk):
    from repro.kernels import ops, ref
    from repro.core.placement import make_scan_mesh
    d, b, c, k, v, m = _stacked_inputs(rng)
    ndv = (5, 3)
    mesh = make_scan_mesh(d.shape[0])
    got = ops.sharded_scan_agg(d, b, c, 15, 55, k, v, ndv=ndv, block_mask=m,
                               mesh=mesh, topk=topk)
    want = ref.ref_sharded_scan_agg(d, b, c, 15, 55, k, v, ndv, m, topk=topk)
    if topk:
        gids, gc, gs, gmn, gmx, gtot = [np.asarray(x) for x in got]
        wids, wc, ws, wmn, wmx, wtot = [np.asarray(x) for x in want]
        np.testing.assert_array_equal(gids, wids)
        np.testing.assert_array_equal(gc, wc)
        assert int(gtot) == int(wtot)
        live = gc > 0
        np.testing.assert_allclose(gs[:, live], ws[:, live],
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(gmn[:, live], wmn[:, live], rtol=1e-5)
        np.testing.assert_allclose(gmx[:, live], wmx[:, live], rtol=1e-5)
    else:
        gc, gs, gmn, gmx = [np.asarray(x) for x in got]
        wc, ws, wmn, wmx = [np.asarray(x) for x in want]
        np.testing.assert_array_equal(gc, wc)
        live = gc > 0
        np.testing.assert_allclose(gs[:, live], ws[:, live],
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(gmn[:, live], wmn[:, live], rtol=1e-5)
        np.testing.assert_allclose(gmx[:, live], wmx[:, live], rtol=1e-5)


@pytest.mark.device
def test_sharded_scan_agg_coalesced_tiles(rng):
    """Tile-fused collective launch (factor dividing the padded shard
    width) equals the unfused launch.  The pruned block's rows sit outside
    the predicate window, as a real zone-map NONE verdict guarantees
    (tile fusing ORs member masks and relies on the window re-filter)."""
    from repro.kernels import ops
    from repro.core.placement import make_scan_mesh
    d, b, c, k, v, m = _stacked_inputs(rng, S=2, Nb=4)
    d[0, 1] = 500                                # masked block: no matches
    mesh = make_scan_mesh(2)
    base = ops.sharded_scan_agg(d, b, c, 10, 50, k, v, ndv=(5, 3),
                                block_mask=m, mesh=mesh)
    fused = ops.sharded_scan_agg(d, b, c, 10, 50, k, v, ndv=(5, 3),
                                 block_mask=m, mesh=mesh, coalesce=2)
    np.testing.assert_array_equal(np.asarray(base[0]), np.asarray(fused[0]))
    np.testing.assert_allclose(np.asarray(base[1]), np.asarray(fused[1]),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# executor-level: collective route parity (1/2/4 shards, interpret mode)
# ---------------------------------------------------------------------------


DEVICE_QUERIES = [
    Query(preds=(Predicate("d", PredOp.BETWEEN, 50, 250),),
          group_by=("g",),
          aggs=(QAgg("count", None, "n"), QAgg("sum", "v", "sv"),
                QAgg("min", "v", "mn"), QAgg("max", "v", "mx"))),
    Query(group_by=("g", "s"),                    # q2 shape, string dict key
          aggs=(QAgg("count", None, "n"), QAgg("avg", "v", "av"))),
]


@pytest.mark.device
@pytest.mark.parametrize("qi", range(len(DEVICE_QUERIES)))
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_collective_route_parity(qi, shards):
    """shard_map collective route ≡ per-shard host-merge route ≡
    VectorEngine, for every shard count (the single-device mesh runs all
    shard slices in one launch; a multi-device mesh splits them)."""
    rng = np.random.default_rng(41 * (qi + 1) + shards)
    store = make_store(rng, n=384, block_rows=64, dml=False)
    q = DEVICE_QUERIES[qi]
    table, _ = store.scan()

    def key_of(r):
        return tuple(r[g].decode() if isinstance(r[g], bytes) else r[g]
                     for g in q.group_by)

    want_k = {key_of(r): r for r in VectorEngine().execute(table, q)}
    for route in ("collective", "host"):
        ex = ShardedScanExecutor(n_shards=shards, device=True,
                                 device_route=route)
        rows, stats = ex.execute_stats(store, q)
        assert stats.used_device and stats.device_route == route
        assert stats.n_devices >= 1
        got = {key_of(r): r for r in rows}
        assert got.keys() == want_k.keys(), route
        for k, w in want_k.items():
            for a in q.aggs:
                if a.op == "count":
                    assert got[k][a.alias] == w[a.alias], (route, k)
                else:
                    np.testing.assert_allclose(got[k][a.alias], w[a.alias],
                                               atol=1e-3, rtol=1e-4)


@pytest.mark.device
def test_collective_route_fallbacks():
    """Merge-on-read DML and NULL-bearing aggregate columns force the host
    scan path — answers stay correct either way."""
    q = Query(preds=(Predicate("d", PredOp.BETWEEN, 20, 300),),
              group_by=("g",), aggs=(QAgg("count", None, "n"),
                                     QAgg("sum", "v", "sv")))
    # DML: device path refuses (row-format increments are host-only)
    store = make_store(np.random.default_rng(43), n=256, block_rows=64,
                       dml=True)
    rows, stats = ShardedScanExecutor(
        n_shards=2, device=True,
        device_route="collective").execute_stats(store, q)
    assert not stats.used_device
    table, _ = store.scan()
    assert norm(rows) == norm(VectorEngine().execute(table, q))
    # NULLs in the aggregated column: plan_device bails, host path answers
    nstore = make_null_store(np.random.default_rng(44), inc=False)
    q2 = Query(preds=(Predicate("d", PredOp.BETWEEN, 10, 80),),
               group_by=("g",), aggs=(QAgg("count", "v", "cv"),
                                      QAgg("sum", "v", "sv")))
    rows2, stats2 = ShardedScanExecutor(
        n_shards=2, device=True,
        device_route="collective").execute_stats(nstore, q2)
    assert not stats2.used_device
    t2, _ = nstore.scan()
    assert norm(rows2) == norm(VectorEngine().execute(t2, q2))


@pytest.mark.device
def test_collective_route_multi_device_subprocess():
    """On a real 4-device 'scan' mesh the collective route splits the shard
    axis across devices and psum-reduces; parity with the host executor
    must hold for shard counts that do and do not divide the mesh, and
    every answer runs on all four devices, whatever the shard count."""
    from tests.test_distributed import run_py
    out = run_py("""
        import numpy as np
        from repro.core.engine import QAgg, Query
        from repro.core.partition import ShardedScanExecutor
        from repro.core.relation import Predicate, PredOp
        import sys; sys.path.insert(0, ".")
        from tests.test_pushdown import make_store
        store = make_store(np.random.default_rng(13), n=512, block_rows=32,
                           dml=False)
        q = Query(preds=(Predicate("d", PredOp.BETWEEN, 50, 250),),
                  group_by=("g",),
                  aggs=(QAgg("count", None, "n"), QAgg("sum", "v", "sv")))
        host = {r["g"]: r for r in
                ShardedScanExecutor(n_shards=4).execute(store, q)}
        for shards in (2, 4, 6):
            ex = ShardedScanExecutor(n_shards=shards, device=True,
                                     device_route="collective")
            rows, st = ex.execute_stats(store, q)
            assert st.used_device and st.n_devices == 4, st
            assert st.n_shards % 4 == 0 and st.n_shards >= shards, st
            dm = {r["g"]: r for r in rows}
            assert dm.keys() == host.keys()
            for g in host:
                assert dm[g]["n"] == host[g]["n"]
                np.testing.assert_allclose(dm[g]["sv"], host[g]["sv"],
                                           atol=1e-3, rtol=1e-4)
        # cost-chosen route on a multi-device mesh is the collective
        _, st = ShardedScanExecutor(n_shards=4,
                                    device=True).execute_stats(store, q)
        assert st.device_route == "collective" and st.n_devices == 4, st
        print("MULTIDEV_OK")
    """, ndev=4)
    assert "MULTIDEV_OK" in out


# ---------------------------------------------------------------------------
# limit-aware top-k pushdown (host heaps + device accumulator slice)
# ---------------------------------------------------------------------------


def _tie_store(rng, n=400, block_rows=32):
    """Low-cardinality leading sort key -> lots of cross-shard ties that
    must break deterministically (by the remaining group columns)."""
    sch = schema(("k", ColType.INT), ("g", ColType.INT), ("d", ColType.INT),
                 ("v", ColType.FLOAT))
    store = LSMStore(sch, block_rows=block_rows, memtable_limit=10**6)
    for i in range(n):
        store.insert({"k": i, "g": int(rng.integers(0, 3)),
                      "d": int(rng.integers(0, 40)),
                      "v": float(rng.normal())})
    store.major_compact()
    return store


TOPK_QUERIES = [
    # leading-prefix sort: per-shard from_columns truncates pre-accumulation
    Query(group_by=("g", "d"), aggs=(QAgg("count", None, "n"),
                                     QAgg("sum", "v", "sv")),
          sort_by=("g", "d"), limit=7),
    # tie-heavy: sort key is a strict subset of the group columns
    Query(group_by=("g", "d"), aggs=(QAgg("count", None, "n"),),
          sort_by=("g",), limit=5),
    Query(preds=(Predicate("d", PredOp.LT, 25),), group_by=("d",),
          aggs=(QAgg("min", "v", "mn"), QAgg("max", "v", "mx")),
          sort_by=("d",), limit=3),
]


@pytest.mark.parametrize("qi", range(len(TOPK_QUERIES)))
@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("dml", [False, True])
def test_topk_pushdown_parity_with_ties(qi, shards, dml):
    q = TOPK_QUERIES[qi]
    rng = np.random.default_rng(7 * (qi + 1) + shards + 10 * dml)
    if dml:
        store = make_store(rng, dml=True)
    else:
        store = _tie_store(rng)
    table, _ = store.scan()
    want = norm(VectorEngine().execute(table, q))
    full = ShardedScanExecutor(n_shards=shards, limit_pushdown=False)
    push = ShardedScanExecutor(n_shards=shards)
    assert norm(full.execute(store, q)) == want
    rows, stats = push.execute_stats(store, q)
    assert norm(rows) == want
    assert stats.topk_pushdown


def test_topk_not_pushable_for_aggregate_sort():
    """Sorting by an aggregate alias (rank unknown before the merge) keeps
    the full-merge path and the answer."""
    q = Query(group_by=("g",), aggs=(QAgg("sum", "v", "sv"),),
              sort_by=("sv",), limit=3)
    assert topk_group_limit(q) is None
    store = _tie_store(np.random.default_rng(3))
    table, _ = store.scan()
    rows, stats = ShardedScanExecutor(n_shards=3).execute_stats(store, q)
    assert not stats.topk_pushdown
    assert norm(rows) == norm(VectorEngine().execute(table, q))


def test_topk_projection_gather_parity():
    """Projection top-k: per-shard row heaps, stable tie-break by original
    row position across shard boundaries and incremental rows."""
    q = Query(preds=(Predicate("d", PredOp.LT, 30),),
              project=("k", "g", "d"), sort_by=("g", "d"), limit=9)
    for dml in (False, True):
        store = make_store(np.random.default_rng(5 + dml), dml=dml)
        table, _ = store.scan()
        want = [tuple(sorted(r.items()))
                for r in VectorEngine().execute(table, q)]
        for shards in (1, 2, 4):
            push = ShardedScanExecutor(n_shards=shards)
            rows, stats = push.execute_stats(store, q)
            got = [tuple(sorted(r.items())) for r in rows]
            assert got == want, (dml, shards)    # ordered compare: ties too
            assert stats.topk_pushdown


def test_grouped_partial_topk_truncation_and_merge():
    """Per-shard heaps merge to the same top-k the full merge reaches."""
    q = Query(group_by=("g",), aggs=(QAgg("sum", "v", "sv"),),
              sort_by=("g",), limit=3)
    rng = np.random.default_rng(11)
    g = rng.integers(0, 20, 300)
    v = rng.normal(size=300)
    halves = [GroupedPartial.from_columns(
        q, {"g": g[i::2], "v": v[i::2]}, 150) for i in range(2)]
    whole = GroupedPartial.from_columns(q, {"g": g, "v": v}, 300)
    lhs = tree_reduce([p.topk(q, 3) for p in halves],
                      lambda a, b: GroupedPartial.merge(a, b).topk(q, 3))
    assert lhs.keys == whole.topk(q, 3).keys
    assert norm(lhs.finalize(q)) == norm(whole.finalize(q))
    # prefix fast path built the same partial the generic path would
    pre = GroupedPartial.from_columns(q, {"g": g, "v": v}, 300,
                                     topk_prefix=3)
    assert pre.keys == whole.topk(q, 3).keys
    np.testing.assert_allclose(pre.sums["v"], whole.topk(q, 3).sums["v"])


@pytest.mark.device
def test_topk_device_accumulator_slice():
    """Collective route + pushable top-k: the accumulator is sliced on
    device (only k groups reach the host) and matches the unpushed
    answer."""
    store = make_store(np.random.default_rng(17), n=384, block_rows=64,
                       dml=False)
    q = Query(preds=(Predicate("d", PredOp.BETWEEN, 30, 330),),
              group_by=("g",), aggs=(QAgg("count", None, "n"),
                                     QAgg("sum", "v", "sv")),
              sort_by=("g",), limit=3)
    want = ShardedScanExecutor(n_shards=2, limit_pushdown=False
                               ).execute(store, q)
    ex = ShardedScanExecutor(n_shards=2, device=True,
                             device_route="collective")
    rows, stats = ex.execute_stats(store, q)
    assert stats.used_device and stats.topk_pushdown
    assert [r["g"] for r in rows] == [r["g"] for r in want]
    for a, b in zip(rows, want):
        assert a["n"] == b["n"]
        np.testing.assert_allclose(a["sv"], b["sv"], atol=1e-3, rtol=1e-4)
