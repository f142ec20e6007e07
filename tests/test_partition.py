"""Mesh-sharded scan fan-out (core/partition.py): range partitioning from
leaf sketches, Sketch.merge-style partial combination, tree reduction, and
the headline contract — ``ShardedScanExecutor`` over the LSM store returns
the same rows as ``VectorEngine`` over the fully decoded ``store.scan()``
for ANY shard count, including merge-on-read deletes/updates and unmerged
incremental data."""
import numpy as np
import pytest

from repro.core.engine import QAgg, Query, VectorEngine, make_engine
from repro.core.lsm import LSMStore
from repro.core.partition import (BlockShard, GroupedPartial,
                                  ShardedScanExecutor, range_partition,
                                  tree_reduce)
from repro.core.pushdown import PushdownExecutor
from repro.core.relation import ColType, Predicate, PredOp, schema

from tests.test_pushdown import QUERIES, make_store, norm


# ---------------------------------------------------------------------------
# shard-count parity sweep (tentpole acceptance)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qi", range(len(QUERIES)))
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_sharded_parity_vs_vector_engine_with_dml(qi, shards):
    """1/2/4-shard fan-out ≡ VectorEngine over a store with deletes,
    updates and unmerged incremental rows (merge-on-read)."""
    rng = np.random.default_rng(17 * (qi + 1) + shards)
    store = make_store(rng, dml=True)
    q = QUERIES[qi]
    table, _ = store.scan()
    got, stats = ShardedScanExecutor(n_shards=shards).execute_stats(store, q)
    assert norm(got) == norm(VectorEngine().execute(table, q))
    assert stats.n_shards == shards
    assert stats.rows_merged_incremental > 0


@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_sharded_parity_clean_baseline(qi):
    rng = np.random.default_rng(5 * (qi + 1))
    store = make_store(rng, dml=False)
    q = QUERIES[qi]
    table, _ = store.scan()
    want = norm(VectorEngine().execute(table, q))
    for shards in (1, 3, 8):
        assert norm(ShardedScanExecutor(n_shards=shards).execute(store, q)) \
            == want


def test_sharded_more_shards_than_blocks():
    """Empty shards (more shards than baseline blocks) are harmless."""
    rng = np.random.default_rng(2)
    store = make_store(rng, n=64, block_rows=32, dml=True)
    q = QUERIES[0]
    table, _ = store.scan()
    got = ShardedScanExecutor(n_shards=16).execute(store, q)
    assert norm(got) == norm(VectorEngine().execute(table, q))


def test_sharded_empty_store():
    sch = schema(("k", ColType.INT), ("v", ColType.FLOAT))
    store = LSMStore(sch, block_rows=16)
    q = Query(aggs=(QAgg("count", None, "n"), QAgg("sum", "v", "sv"),
                    QAgg("min", "v", "mn")))
    rows = ShardedScanExecutor(n_shards=4).execute(store, q)
    assert rows == [{"n": 0, "sv": 0, "mn": None}]


# ---------------------------------------------------------------------------
# degenerate shard shapes: all-empty shards, shards > blocks, everything
# pruned in every shard (flat and grouped)
# ---------------------------------------------------------------------------


def test_merge_over_all_empty_partials():
    q = Query(group_by=("g",), aggs=(QAgg("count", None, "n"),
                                     QAgg("sum", "v", "sv"),
                                     QAgg("min", "v", "mn")))
    empties = [GroupedPartial.from_columns(
        q, {"g": np.empty(0, np.int64), "v": np.empty(0)}, 0)
        for _ in range(5)]
    merged = tree_reduce(empties, GroupedPartial.merge)
    assert merged.keys == [] and merged.finalize(q) == []
    # flat shape: empty partials still emit the typed empty-aggregate row
    qf = Query(aggs=(QAgg("count", None, "n"), QAgg("sum", "v", "sv"),
                     QAgg("min", "v", "mn"), QAgg("avg", "v", "av")))
    flat = [GroupedPartial.from_columns(q=qf, cols={"v": np.empty(0)},
                                        n_rows=0) for _ in range(4)]
    assert tree_reduce(flat, GroupedPartial.merge).finalize(qf) == \
        [{"n": 0, "sv": 0, "mn": None, "av": None}]


@pytest.mark.parametrize("grouped", [False, True])
@pytest.mark.parametrize("shards", [1, 3, 16])
def test_predicate_prunes_every_block_in_every_shard(grouped, shards):
    """A predicate outside every zone map: every shard's block range prunes
    to nothing; flat and grouped fan-outs must still emit VectorEngine's
    empty-result convention."""
    rng = np.random.default_rng(4)
    store = make_store(rng, n=256, block_rows=32, dml=False)
    preds = (Predicate("d", PredOp.GT, 10_000),)
    q = (Query(preds=preds, group_by=("g",),
               aggs=(QAgg("count", None, "n"), QAgg("sum", "v", "sv")))
         if grouped else
         Query(preds=preds, aggs=(QAgg("count", None, "n"),
                                  QAgg("sum", "v", "sv"),
                                  QAgg("min", "v", "mn"))))
    ex = ShardedScanExecutor(n_shards=shards)
    rows, stats = ex.execute_stats(store, q)
    table, _ = store.scan()
    assert norm(rows) == norm(VectorEngine().execute(table, q))
    assert stats.blocks_skipped == store.baseline.n_blocks
    assert stats.blocks_scanned == 0


def test_more_shards_than_blocks_grouped_and_flat():
    rng = np.random.default_rng(6)
    store = make_store(rng, n=96, block_rows=32, dml=True)
    table, _ = store.scan()
    for q in (Query(group_by=("g",), aggs=(QAgg("count", None, "n"),
                                           QAgg("max", "v", "mx"))),
              Query(aggs=(QAgg("count", None, "n"),
                          QAgg("sum", "v", "sv")))):
        got = ShardedScanExecutor(n_shards=12).execute(store, q)
        assert norm(got) == norm(VectorEngine().execute(table, q))


def test_make_engine_sharded():
    eng = make_engine("sharded", n_shards=3)
    assert eng.name == "sharded" and eng.n_shards == 3


# ---------------------------------------------------------------------------
# range partitioning
# ---------------------------------------------------------------------------


def test_range_partition_contiguous_and_balanced():
    rng = np.random.default_rng(9)
    store = make_store(rng, n=1024, block_rows=32, dml=False)
    base = store.baseline
    for k in (1, 2, 4, 7):
        shards = range_partition(base, k)
        assert len(shards) == k
        assert shards[0].lo_block == 0 and shards[-1].hi_block == base.n_blocks
        for a, b in zip(shards, shards[1:]):
            assert a.hi_block == b.lo_block          # contiguous, disjoint
        assert sum(s.n_rows for s in shards) == base.nrows
        # leaf-sketch weighting keeps shards within one block of even
        assert max(s.n_rows for s in shards) <= base.nrows / k + 32


def test_range_partition_empty_baseline():
    sch = schema(("k", ColType.INT), ("v", ColType.FLOAT))
    store = LSMStore(sch)
    shards = range_partition(store.baseline, 4)
    assert [s.n_blocks for s in shards] == [0, 0, 0, 0]


def test_tree_reduce_topology_and_value():
    assert tree_reduce([1, 2, 3, 4, 5], lambda a, b: a + b) == 15
    assert tree_reduce(["a"], lambda a, b: a + b) == "a"
    pairs = []
    tree_reduce([[1], [2], [3], [4]],
                lambda a, b: (pairs.append((a[0], b[0])), [a[0] + b[0]])[1])
    assert pairs == [(1, 2), (3, 4), (3, 7)]     # balanced binary tree
    with pytest.raises(ValueError):
        tree_reduce([], lambda a, b: a)


# ---------------------------------------------------------------------------
# GroupedPartial combination (Sketch.merge-style)
# ---------------------------------------------------------------------------


def test_grouped_partial_merge_equals_whole():
    """Aggregating two halves and merging == aggregating the whole."""
    rng = np.random.default_rng(11)
    q = Query(group_by=("g",),
              aggs=(QAgg("count", None, "n"), QAgg("sum", "v", "sv"),
                    QAgg("min", "v", "mn"), QAgg("max", "v", "mx")))
    g = rng.integers(0, 5, 200)
    v = rng.normal(size=200)
    whole = GroupedPartial.from_columns(q, {"g": g, "v": v}, 200)
    left = GroupedPartial.from_columns(q, {"g": g[:90], "v": v[:90]}, 90)
    right = GroupedPartial.from_columns(q, {"g": g[90:], "v": v[90:]}, 110)
    merged = GroupedPartial.merge(left, right)
    assert merged.keys == whole.keys
    np.testing.assert_array_equal(merged.rows_per_group, whole.rows_per_group)
    np.testing.assert_allclose(merged.sums["v"], whole.sums["v"], rtol=1e-12)
    np.testing.assert_array_equal(merged.mins["v"], whole.mins["v"])
    np.testing.assert_array_equal(merged.maxs["v"], whole.maxs["v"])
    assert norm(merged.finalize(q)) == norm(whole.finalize(q))


def test_grouped_partial_merge_disjoint_keys_and_empty():
    q = Query(group_by=("g",), aggs=(QAgg("sum", "v", "sv"),
                                     QAgg("min", "v", "mn")))
    a = GroupedPartial.from_columns(
        q, {"g": np.asarray([1, 1]), "v": np.asarray([1.0, 2.0])}, 2)
    b = GroupedPartial.from_columns(
        q, {"g": np.asarray([3]), "v": np.asarray([7.0])}, 1)
    empty = GroupedPartial.from_columns(
        q, {"g": np.empty(0, np.int64), "v": np.empty(0)}, 0)
    m = tree_reduce([a, empty, b], GroupedPartial.merge)
    assert m.keys == [(1,), (3,)]
    np.testing.assert_allclose(m.sums["v"], [3.0, 7.0])
    np.testing.assert_allclose(m.mins["v"], [1.0, 7.0])


def test_grouped_partial_flat_int_sum_exact():
    """Flat int sums stay int64 through the merge tree (exact, typed like
    VectorEngine's flat aggregation)."""
    q = Query(aggs=(QAgg("sum", "d", "sd"), QAgg("count", None, "n")))
    parts = [GroupedPartial.from_columns(
        q, {"d": np.asarray([2**40, i])}, 2) for i in range(5)]
    rows = tree_reduce(parts, GroupedPartial.merge).finalize(q)
    assert rows == [{"sd": 5 * 2**40 + 10, "n": 10}]
    assert isinstance(rows[0]["sd"], int)


# ---------------------------------------------------------------------------
# device fan-out (fused kernel per shard, interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.device
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_sharded_device_fanout_matches_host(shards):
    rng = np.random.default_rng(13)
    store = make_store(rng, n=256, block_rows=64, dml=False)
    q = Query(preds=(Predicate("d", PredOp.BETWEEN, 50, 250),),
              group_by=("g",),
              aggs=(QAgg("count", None, "n"), QAgg("sum", "v", "sv"),
                    QAgg("avg", "v", "av")))
    host = {r["g"]: r for r in ShardedScanExecutor(n_shards=shards
                                                   ).execute(store, q)}
    ex = ShardedScanExecutor(n_shards=shards, device=True)
    rows, stats = ex.execute_stats(store, q)
    assert stats.used_device and stats.n_shards == shards
    dev = {r["g"]: r for r in rows}
    assert host.keys() == dev.keys()
    for g in host:
        assert host[g]["n"] == dev[g]["n"]
        np.testing.assert_allclose(dev[g]["sv"], host[g]["sv"],
                                   atol=1e-3, rtol=1e-4)
        np.testing.assert_allclose(dev[g]["av"], host[g]["av"],
                                   atol=1e-3, rtol=1e-4)


@pytest.mark.device
def test_sharded_device_falls_back_with_incremental():
    """Merge-on-read data forces the host path (device partials can't see
    row-format increments) — answers stay correct."""
    rng = np.random.default_rng(14)
    store = make_store(rng, n=256, block_rows=64, dml=True)
    q = Query(preds=(Predicate("d", PredOp.BETWEEN, 50, 250),),
              group_by=("g",), aggs=(QAgg("count", None, "n"),))
    rows, stats = ShardedScanExecutor(n_shards=2,
                                      device=True).execute_stats(store, q)
    assert not stats.used_device
    table, _ = store.scan()
    assert norm(rows) == norm(VectorEngine().execute(table, q))


def test_scan_mesh_shard_devices():
    from repro.core.placement import make_scan_mesh, scan_shard_devices
    mesh = make_scan_mesh(4)
    assert mesh.axis_names == ("scan",)
    devs = scan_shard_devices(4, mesh)
    assert len(devs) == 4 and all(d is not None for d in devs)
