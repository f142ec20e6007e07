"""Fault-tolerant execution: taxonomy, retry/hedging/deadlines, block
corruption, and the route-degradation parity matrix.

The contract under test (the "continuous availability" claim): under every
injected single-fault scenario a query either

* returns results identical to the clean run, with the degradation step
  recorded in ``ScanStats.degraded`` / ``Plan.degraded`` provenance, or
* raises the matching typed :class:`~repro.core.errors.QueryError` —
  never a silently wrong answer, never a bare ``RuntimeError``.

Every scenario is driven by a deterministic :class:`FaultPlan` (faults key
on shard ids / attempt numbers / call ordinals, never wall clock), so the
matrix replays identically run to run.
"""
import numpy as np
import pytest

from repro.core import faultinject
from repro.core.engine import QAgg, Query, VectorEngine
from repro.core.errors import (BlockCorruption, Deadline, KernelLaunchError,
                               KeyPackError, MLogPurged, QueryError,
                               QueryTimeout, RouteExhausted, ShardFailure)
from repro.core.faultinject import (FaultPlan, corrupt_block, corrupt_replica,
                                    inject)
from repro.core.health import Breaker
from repro.core.lsm import LSMStore
from repro.core.mview import AggSpec, MAVDefinition
from repro.core.partition import ShardedScanExecutor
from repro.core.pushdown import PushdownExecutor
from repro.core.relation import ColType, Predicate, PredOp
from repro.core.replica import enable_replication, replica_set
from repro.core.session import Database

from tests.test_pushdown import QUERIES, SCH, make_store, norm

GROUPED_Q = Query(preds=(Predicate("d", PredOp.BETWEEN, 50, 300),),
                  group_by=("g",),
                  aggs=(QAgg("count", None, "n"), QAgg("sum", "v", "sv")))
DEVICE_Q = Query(preds=(Predicate("d", PredOp.BETWEEN, 50, 250),),
                 group_by=("g",),
                 aggs=(QAgg("count", None, "n"), QAgg("sum", "v", "sv")))


def sharded(**kw):
    kw.setdefault("n_shards", 4)
    kw.setdefault("max_workers", 4)
    kw.setdefault("retry_backoff_s", 0.001)
    return ShardedScanExecutor(**kw)


# ---------------------------------------------------------------------------
# taxonomy
# ---------------------------------------------------------------------------


def test_error_taxonomy():
    for cls in (ShardFailure, BlockCorruption, KernelLaunchError,
                QueryTimeout, RouteExhausted, MLogPurged, KeyPackError):
        assert issubclass(cls, QueryError)
    # back-compat contracts: callers catching the pre-taxonomy types
    assert issubclass(MLogPurged, RuntimeError)
    assert issubclass(KeyPackError, ValueError)
    e = ShardFailure(3, 2, RuntimeError("boom"))
    assert e.shard_id == 3 and "after 2 attempt(s)" in str(e)
    t = QueryTimeout(0.5, 0.7, completed=2, total=4)
    assert "2/4 shards" in str(t) and t.deadline_s == 0.5
    r = RouteExhausted(["a->b: x"], ValueError("y"))
    assert r.steps == ["a->b: x"] and "a->b: x" in str(r)


def test_mlog_purged_importable_from_legacy_homes():
    from repro.core import MLogPurged as a
    from repro.core.mview import MLogPurged as b
    assert a is b is MLogPurged


def test_deadline_primitive():
    assert Deadline.start(None) is None
    d = Deadline.start(30.0)
    assert not d.expired() and 0 < d.elapsed() < d.seconds
    assert Deadline.start(0.0).expired()


# ---------------------------------------------------------------------------
# clean path: an installed-but-empty plan changes nothing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_empty_fault_plan_is_transparent(qi):
    rng = np.random.default_rng(31 + qi)
    store = make_store(rng)
    q = QUERIES[qi]
    for ex in (PushdownExecutor(), sharded()):
        clean, cstats = ex.execute_stats(store, q)
        with inject(FaultPlan()) as fp:
            rows, stats = ex.execute_stats(store, q)
        assert rows == clean
        assert fp.events == []
        assert stats.degraded == [] and cstats.degraded == []
        assert stats.shard_retries == 0 and stats.hedges == 0


def test_inject_restores_previous_plan():
    assert faultinject.active() is None
    with inject(FaultPlan()) as outer:
        assert faultinject.active() is outer
        with inject(FaultPlan()) as inner:
            assert faultinject.active() is inner
        assert faultinject.active() is outer
    assert faultinject.active() is None


# ---------------------------------------------------------------------------
# shard retry / hedging / deadlines (host fan-out)
# ---------------------------------------------------------------------------


def test_transient_shard_fault_retries_to_identical_result():
    rng = np.random.default_rng(41)
    store = make_store(rng)
    ex = sharded()
    clean, _ = ex.execute_stats(store, GROUPED_Q)
    with inject(FaultPlan(fail_shard={1: 1})) as fp:
        rows, stats = ex.execute_stats(store, GROUPED_Q)
    assert rows == clean                      # bit-identical: same merge order
    assert stats.shard_retries >= 1
    assert stats.degraded == []               # retry absorbed the fault
    assert fp.events == ["fail shard 1 attempt 0"]


def test_transient_shard_fault_serial_path():
    rng = np.random.default_rng(42)
    store = make_store(rng)
    ex = sharded(max_workers=1)
    clean, _ = ex.execute_stats(store, GROUPED_Q)
    with inject(FaultPlan(fail_shard={2: 2})):
        rows, stats = ex.execute_stats(store, GROUPED_Q)
    assert rows == clean and stats.shard_retries == 2


def test_exhausted_shard_degrades_to_vectorized():
    rng = np.random.default_rng(43)
    store = make_store(rng)
    ex = sharded(max_attempts=2)
    clean, _ = ex.execute_stats(store, GROUPED_Q)
    with inject(FaultPlan(fail_shard={1: 99})) as fp:
        rows, stats = ex.execute_stats(store, GROUPED_Q)
    assert norm(rows) == norm(clean)          # cross-engine: float tolerance
    assert len(stats.degraded) == 1
    assert stats.degraded[0].startswith("sharded->vectorized: ShardFailure")
    assert "shard 1" in stats.degraded[0]
    assert fp.events == ["fail shard 1 attempt 0", "fail shard 1 attempt 1"]


def test_straggler_hedge_wins_with_identical_result():
    rng = np.random.default_rng(44)
    store = make_store(rng)
    ex = sharded()
    clean, _ = ex.execute_stats(store, GROUPED_Q)
    with inject(FaultPlan(delay_shard={0: 1.5})) as fp:
        rows, stats = ex.execute_stats(store, GROUPED_Q)
    assert rows == clean                      # position-indexed merge order
    assert stats.hedges == 1
    assert stats.degraded == []               # hedging is not a degradation
    assert fp.events == ["delay shard 0 by 1.500s"]


def test_failing_shard_is_retried_not_hedged():
    """A shard backing off between failed attempts is the retry loop's,
    not a straggler: however long the backoff outlasts the other shards,
    no hedge twin runs it, so the fault's outcome does not hang on
    timing."""
    rng = np.random.default_rng(46)
    store = make_store(rng)
    ex = sharded(retry_backoff_s=0.3, max_attempts=3)
    clean, _ = ex.execute_stats(store, GROUPED_Q)
    with inject(FaultPlan(fail_shard={1: 2})) as fp:
        rows, stats = ex.execute_stats(store, GROUPED_Q)
    assert rows == clean
    assert stats.hedges == 0 and stats.shard_retries == 2
    assert fp.events == ["fail shard 1 attempt 0", "fail shard 1 attempt 1"]


def test_deadline_raises_query_timeout_with_partial_progress():
    rng = np.random.default_rng(45)
    store = make_store(rng)
    ex = sharded(hedge=False)
    delays = {i: 0.8 for i in range(4)}
    with inject(FaultPlan(delay_shard=delays)):
        with pytest.raises(QueryTimeout) as ei:
            ex.execute_stats(store, GROUPED_Q, deadline_s=0.15)
    e = ei.value
    assert e.deadline_s == pytest.approx(0.15)
    assert e.elapsed_s >= 0.15
    assert e.total == 4 and 0 <= e.completed < 4
    assert e.stats is not None               # partial-progress ScanStats


def test_deadline_via_database_session():
    rng = np.random.default_rng(46)
    db = Database(make_store(rng), max_workers=4)
    with inject(FaultPlan(delay_shard={i: 0.8 for i in range(4)})):
        with pytest.raises(QueryTimeout):
            db.query(GROUPED_Q, engine="sharded", n_shards=4,
                     deadline_s=0.15)
    # no deadline: the same query completes
    rs = db.query(GROUPED_Q, engine="sharded", n_shards=4)
    assert len(rs) > 0


def test_generous_deadline_is_harmless():
    rng = np.random.default_rng(47)
    store = make_store(rng)
    for ex in (PushdownExecutor(), sharded()):
        clean, _ = ex.execute_stats(store, GROUPED_Q)
        rows, stats = ex.execute_stats(store, GROUPED_Q, deadline_s=60.0)
        assert rows == clean and stats.degraded == []


# ---------------------------------------------------------------------------
# block corruption: checksums, quarantine, MAV exclusion
# ---------------------------------------------------------------------------


def test_corrupt_block_raises_block_corruption_and_quarantines():
    rng = np.random.default_rng(51)
    store = make_store(rng, dml=False)
    corrupt_block(store, "v", block=1)
    # a grouped aggregate must decode 'v' — flat sketches would mask it
    with pytest.raises(BlockCorruption) as ei:
        PushdownExecutor().execute(store, GROUPED_Q)
    e = ei.value
    assert e.column == "v" and e.block == 1
    assert e.expected != e.actual
    assert 1 in store.baseline.cols["v"].quarantined
    assert store.has_quarantined_blocks()


def test_corruption_is_never_retried_on_sharded_route():
    rng = np.random.default_rng(52)
    store = make_store(rng, dml=False)
    corrupt_block(store, "v", block=0)
    ex = sharded()
    with pytest.raises(BlockCorruption):
        ex.execute_stats(store, GROUPED_Q)
    assert ex.last_stats.shard_retries == 0   # deterministic: no retry
    assert ex.last_stats.degraded == []       # and no vectorized fallback


def test_clean_blocks_still_readable_after_quarantine():
    rng = np.random.default_rng(53)
    store = make_store(rng, dml=False)
    corrupt_block(store, "v", block=0)
    cst = store.baseline.cols["v"]
    with pytest.raises(BlockCorruption):
        cst.decode_block(0)
    # the fault is per-block: every other block still verifies
    for b in range(1, len(cst.blocks)):
        cst.decode_block(b)
    assert cst.quarantined == {0}


def test_quarantine_excludes_mav_rewrite():
    rng = np.random.default_rng(54)
    db = Database(make_store(rng, dml=False))
    q = Query(group_by=("g",), aggs=(QAgg("sum", "v", "sv"),))
    db.create_mav("mv_g", MAVDefinition(
        group_by=("g",), aggs=(AggSpec("sum", "v", "sv"),)))
    assert db.explain(q).route == "mav"
    corrupt_block(db.table().store, "v", block=0)
    with pytest.raises(BlockCorruption):      # detection quarantines...
        db.query(q, use_mv=False)
    plan = db.explain(q)
    assert plan.route != "mav"                # ...which revokes the rewrite
    with pytest.raises(BlockCorruption):      # and the scan names the block
        db.query(q)


# ---------------------------------------------------------------------------
# mlog faults: bounded retry + purge fallback provenance
# ---------------------------------------------------------------------------


def _mav_db(rng):
    db = Database(make_store(rng, dml=False))
    h = db.table()
    db.create_mav("mv_g", MAVDefinition(
        group_by=("g",), aggs=(AggSpec("sum", "v", "sv"),
                               AggSpec("count_star", None, "n"))))
    for j in range(5000, 5020):               # pending mlog tail
        h.insert({"k": j, "g": int(rng.integers(0, 6)),
                  "d": int(rng.integers(0, 365)), "v": 1.0, "s": "beta"})
    return db


def test_transient_mlog_fault_survived_by_bounded_retry():
    rng = np.random.default_rng(61)
    db = _mav_db(rng)
    q = Query(group_by=("g",), aggs=(QAgg("sum", "v", "sv"),))
    clean = db.query(q)
    assert clean.plan.route == "mav"
    with inject(FaultPlan(mlog_since_failures=1)) as fp:
        rs = db.query(q)
    assert rs.plan.route == "mav"
    assert norm(rs.rows) == norm(clean.rows)
    assert rs.plan.mlog_retries >= 1          # the retry is provenance
    assert not any("purge_fallback" in d for d in rs.plan.degraded)
    assert fp.events == ["transient mlog purge on since() call #1"]


def test_mid_query_purge_falls_back_with_provenance():
    rng = np.random.default_rng(62)
    db = _mav_db(rng)
    q = Query(group_by=("g",), aggs=(QAgg("sum", "v", "sv"),))
    clean = db.query(q, use_mv=False)
    with inject(FaultPlan(purge_mlog_before_read=True)) as fp:
        rs = db.query(q)
    assert rs.plan.route == "mav"             # planned before the purge
    assert norm(rs.rows) == norm(clean.rows)  # full refresh kept it right
    assert rs.stats.purge_fallback
    assert any("purge_fallback" in d for d in rs.plan.degraded)
    assert any(e.startswith("purged mlog mid-query") for e in fp.events)


# ---------------------------------------------------------------------------
# route-degradation parity matrix: scenario × route
# ---------------------------------------------------------------------------

SCENARIOS = [
    ("none", lambda: FaultPlan(), []),
    ("shard-transient", lambda: FaultPlan(fail_shard={1: 1}), []),
    ("shard-exhausted", lambda: FaultPlan(fail_shard={1: 99}),
     ["sharded->vectorized"]),
    ("straggler", lambda: FaultPlan(delay_shard={0: 1.5}), []),
]


@pytest.mark.parametrize("name,mkplan,want_deg",
                         SCENARIOS, ids=[s[0] for s in SCENARIOS])
@pytest.mark.parametrize("route", ["pushdown", "sharded-host"])
def test_fault_matrix_host_routes(route, name, mkplan, want_deg):
    """Single-fault scenarios over the host routes: results match the clean
    run and the degradation trail matches exactly what was injected.  Shard
    faults cannot fire on the single-shard pushdown route — the scenario
    then asserts full transparency."""
    rng = np.random.default_rng(71)
    store = make_store(rng)
    ex = (PushdownExecutor() if route == "pushdown"
          else sharded(max_attempts=2))
    clean, _ = ex.execute_stats(store, GROUPED_Q)
    with inject(mkplan()):
        rows, stats = ex.execute_stats(store, GROUPED_Q)
    if route == "pushdown":
        want_deg = []                         # no shards → nothing fires
    assert norm(rows) == norm(clean)
    assert len(stats.degraded) == len(want_deg)
    for got, want in zip(stats.degraded, want_deg):
        assert got.startswith(want)
    if not want_deg and route == "sharded-host":
        # undegraded runs feed the cost model; degraded ones must not
        assert stats.degraded == []


@pytest.mark.device
@pytest.mark.parametrize("kernel_failures,want_deg,want_retries", [
    (0, [], 0),
    (1, [], 1),                        # in-route retry absorbs one transient
    (2, ["device-collective->per-shard-device"], 1),
    (99, ["device-collective->per-shard-device",
          "per-shard-device->host-pushdown"], 1),
], ids=["clean", "transient-retried", "collective-fails",
        "all-kernels-fail"])
def test_fault_matrix_device_collective(kernel_failures, want_deg,
                                        want_retries):
    """The device ladder: a transient collective failure is retried in-route
    (no rung drop); a second failure drops collective → per-shard launches
    → host pushdown, one recorded step per surviving failure level."""
    rng = np.random.default_rng(72)
    store = make_store(rng, n=256, block_rows=64, dml=False)
    host = ShardedScanExecutor(n_shards=2).execute(store, DEVICE_Q)
    ex = ShardedScanExecutor(n_shards=2, device=True,
                             device_route="collective")
    with inject(FaultPlan(kernel_failures=kernel_failures)):
        rows, stats = ex.execute_stats(store, DEVICE_Q)
    assert len(stats.degraded) == len(want_deg)
    for got, want in zip(stats.degraded, want_deg):
        assert got.startswith(want)
    assert stats.kernel_retries == want_retries
    assert stats.used_device == (kernel_failures < 99)
    if kernel_failures <= 1:
        assert stats.device_route == "collective"
    h = {r["g"]: r for r in host}
    d = {r["g"]: r for r in rows}
    assert h.keys() == d.keys()
    for g in h:
        assert h[g]["n"] == d[g]["n"]
        np.testing.assert_allclose(d[g]["sv"], h[g]["sv"],
                                   atol=1e-3, rtol=1e-4)


@pytest.mark.device
def test_fault_matrix_pushdown_device_degrades_to_host():
    rng = np.random.default_rng(73)
    store = make_store(rng, n=256, block_rows=64, dml=False)
    ex = PushdownExecutor(device=True)
    clean, cstats = ex.execute_stats(store, DEVICE_Q)
    assert cstats.used_device
    with inject(FaultPlan(kernel_failures=1)):
        rows, stats = ex.execute_stats(store, DEVICE_Q)
    assert len(stats.degraded) == 1
    assert stats.degraded[0].startswith("device->host-pushdown")
    assert not stats.used_device
    h = {r["g"]: r for r in clean}
    d = {r["g"]: r for r in rows}
    assert h.keys() == d.keys()
    for g in h:
        assert h[g]["n"] == d[g]["n"]
        np.testing.assert_allclose(d[g]["sv"], h[g]["sv"],
                                   atol=1e-3, rtol=1e-4)


class _Unlowerable(Exception):
    """Stands in for the TPU compiler refusing the kernel."""


def _refusing_kernel(*static):
    import jax

    def kernel(*args, **kw):
        raise _Unlowerable("infer-vector-layout: unsupported shape cast")
    return jax.jit(kernel, static_argnames=static)


def _faulting_kernel(*static):
    """Compiles fine; raises on the device while running."""
    import jax

    def boom(x):
        raise RuntimeError("device fault")

    def kernel(deltas, *args, **kw):
        out = jax.pure_callback(boom, jax.ShapeDtypeStruct((), deltas.dtype),
                                deltas.sum())
        return out, out, out, out
    return jax.jit(kernel, static_argnames=static)


@pytest.mark.device
@pytest.mark.parametrize("path", ["pushdown", "collective", "per-shard"])
def test_kernel_lowering_error_fails_the_query(monkeypatch, path):
    """A kernel the compiler refuses is a failed query, never a silent host
    answer: only ``KernelLaunchError`` walks down the ladder."""
    from repro.kernels import ops
    store = make_store(np.random.default_rng(74), n=256, block_rows=64,
                       dml=False)
    monkeypatch.setattr(ops, "fused_scan_agg",
                        _refusing_kernel("ndv", "coalesce"))
    monkeypatch.setattr(ops, "sharded_scan_agg", _refusing_kernel(
        "ndv", "mesh", "coalesce", "topk"))
    ex = {"pushdown": PushdownExecutor(device=True),
          "collective": sharded(device=True, device_route="collective"),
          "per-shard": sharded(device=True, device_route="host")}[path]
    with pytest.raises(_Unlowerable):
        ex.execute_stats(store, DEVICE_Q)


@pytest.mark.device
def test_device_kernel_compiles_once_per_launch_shape(monkeypatch):
    """Checking compile errors apart from runtime faults costs one compile
    per launch shape, not one per query: repeat queries reuse it."""
    from repro.kernels import ops
    store = make_store(np.random.default_rng(76), n=256, block_rows=64,
                       dml=False)
    real, lowered = ops.fused_scan_agg, []

    class Counting:
        def lower(self, *args, **static):
            lowered.append(static)
            return real.lower(*args, **static)

    monkeypatch.setattr(ops, "fused_scan_agg", Counting())
    ex = PushdownExecutor(device=True)
    first = ex.execute_stats(store, DEVICE_Q)
    for _ in range(3):
        rows, stats = ex.execute_stats(store, DEVICE_Q)
        assert stats.used_device and rows == first[0]
    assert len(lowered) == 1


@pytest.mark.device
@pytest.mark.parametrize("path,first", [
    ("pushdown", "device->host-pushdown"),
    ("collective", "device-collective->per-shard-device"),
    ("per-shard", "per-shard-device->host-pushdown")])
def test_kernel_runtime_fault_degrades_as_launch_error(monkeypatch, path,
                                                       first):
    """A fault raised while a compiled kernel runs is wrapped as
    ``KernelLaunchError`` and degrades to the host scan, like an injected
    launch fault — on every device route."""
    from repro.kernels import ops
    store = make_store(np.random.default_rng(75), n=256, block_rows=64,
                       dml=False)
    clean = PushdownExecutor().execute(store, DEVICE_Q)
    monkeypatch.setattr(ops, "fused_scan_agg",
                        _faulting_kernel("ndv", "coalesce"))
    monkeypatch.setattr(ops, "sharded_scan_agg", _faulting_kernel(
        "ndv", "mesh", "coalesce", "topk"))
    ex = {"pushdown": PushdownExecutor(device=True),
          "collective": sharded(device=True, device_route="collective"),
          "per-shard": sharded(device=True, device_route="host")}[path]
    rows, stats = ex.execute_stats(store, DEVICE_Q)
    assert not stats.used_device
    assert stats.degraded[0].startswith(f"{first}: KernelLaunchError")
    assert norm(rows) == norm(clean)


# ---------------------------------------------------------------------------
# provenance surfaces
# ---------------------------------------------------------------------------


def test_degradation_recorded_in_resultset_provenance():
    rng = np.random.default_rng(81)
    db = Database(make_store(rng), max_workers=4)
    with inject(FaultPlan(fail_shard={1: 99})):
        rs = db.query(GROUPED_Q, engine="sharded", n_shards=4)
    assert any(d.startswith("sharded->vectorized") for d in rs.plan.degraded)
    assert "degraded" in repr(rs)
    assert "degraded=[" in rs.plan.describe()
    # the failure opened *shard 1's* breaker (PR 9: per-(rung, shard), so
    # one bad shard does not condemn the whole fan-out): the next query
    # keeps the sharded route and fail-fasts only the suspect shard,
    # saying so in provenance (note grammar, not a "from->to" failure)
    rs2 = db.query(GROUPED_Q, engine="sharded", n_shards=4)
    assert rs2.plan.degraded == [
        "breaker(sharded[1]) open: shard fail-fast (single attempt)"]
    assert rs2.plan.route == "sharded"
    assert norm(rs2.rows) == norm(rs.rows)
    # with health tracking off the session is stateless: clean runs silent
    db2 = Database(make_store(np.random.default_rng(81)), max_workers=4,
                   health=False)
    rs3 = db2.query(GROUPED_Q, engine="sharded", n_shards=4)
    assert rs3.plan.degraded == [] and "degraded" not in repr(rs3)


def test_deadline_checked_in_merge_on_read_assembly():
    rng = np.random.default_rng(106)
    store = make_store(rng)                   # post-compaction DML tail
    inc = store._incremental_effective(store.current_ts)
    assert inc                                # the scenario needs live rows
    with pytest.raises(QueryTimeout):
        store.live_incremental_rows(inc, GROUPED_Q.preds,
                                    deadline=Deadline.start(0.0))
    # a live deadline is harmless: same rows as the unbounded call
    rows = store.live_incremental_rows(inc, GROUPED_Q.preds,
                                       deadline=Deadline.start(60.0))
    assert rows == store.live_incremental_rows(inc, GROUPED_Q.preds)


def test_zero_deadline_binds_on_device_paths_before_launch():
    """``deadline_s`` must bound the device routes too: an expired deadline
    raises before any kernel is planned or launched."""
    rng = np.random.default_rng(105)
    store = make_store(rng, dml=False)
    for ex in (PushdownExecutor(device=True),
               ShardedScanExecutor(n_shards=2, device=True,
                                   device_route="collective")):
        with pytest.raises(QueryTimeout):
            ex.execute_stats(store, DEVICE_Q, deadline_s=0.0)


def test_route_exhausted_when_fallback_also_fails():
    rng = np.random.default_rng(82)
    store = make_store(rng)
    ex = sharded(max_attempts=1)

    class BoomEngine(VectorEngine):
        def execute(self, table, q):
            raise RuntimeError("fallback engine down")

    ex.engine = BoomEngine()
    with inject(FaultPlan(fail_shard={0: 99, 1: 99, 2: 99, 3: 99})):
        with pytest.raises(RouteExhausted) as ei:
            ex.execute_stats(store, GROUPED_Q)
    e = ei.value
    assert any(s.startswith("sharded->vectorized") for s in e.steps)
    assert isinstance(e.cause, RuntimeError)


# ---------------------------------------------------------------------------
# block replicas: corruption repaired in place
# ---------------------------------------------------------------------------


def replicated_store(rng, k=2, n=256, block_rows=32):
    """A multi-block baseline store running with a k-way replica set."""
    store = LSMStore(SCH, block_rows=block_rows, memtable_limit=64,
                     replication=k)
    for i in range(n):
        store.insert({"k": i, "g": int(rng.integers(0, 6)),
                      "d": int(rng.integers(0, 365)),
                      "v": float(rng.normal()),
                      "s": ["alpha", "alpine", "beta"][int(rng.integers(0, 3))]})
    store.major_compact()
    return store


def test_replication_factor_must_be_at_least_two():
    with pytest.raises(ValueError):
        enable_replication(make_store(np.random.default_rng(90)), k=1)


def test_single_copy_corruption_repaired_bit_identically():
    rng = np.random.default_rng(91)
    store = replicated_store(rng, k=2)
    ex = PushdownExecutor()
    clean, cstats = ex.execute_stats(store, GROUPED_Q)
    assert cstats.repaired == []
    corrupt_block(store, "v", block=1)
    rows, stats = ex.execute_stats(store, GROUPED_Q)
    assert norm(rows) == norm(clean)          # answer as if nothing happened
    assert stats.repaired == ["repaired v/block 1 from replica 0"]
    assert stats.degraded == []               # repair is not a degradation
    assert not store.has_quarantined_blocks()  # quarantine lifted
    # the healed block verifies clean on the next read: no re-repair
    rows2, stats2 = ex.execute_stats(store, GROUPED_Q)
    assert norm(rows2) == norm(clean) and stats2.repaired == []


def test_repair_skips_corrupt_replicas():
    rng = np.random.default_rng(92)
    store = replicated_store(rng, k=3)
    corrupt_block(store, "v", block=0)
    corrupt_replica(store, "v", block=0, replica=0)   # replica 0 bad too
    rows, stats = PushdownExecutor().execute_stats(store, GROUPED_Q)
    assert stats.repaired == ["repaired v/block 0 from replica 1"]
    assert not store.has_quarantined_blocks()


def test_sharded_route_repairs_once_across_shards():
    rng = np.random.default_rng(95)
    store = replicated_store(rng, k=2)
    ex = sharded()
    clean, _ = ex.execute_stats(store, GROUPED_Q)
    corrupt_block(store, "v", block=1)
    rows, stats = ex.execute_stats(store, GROUPED_Q)
    assert norm(rows) == norm(clean)
    assert stats.repaired == ["repaired v/block 1 from replica 0"]
    assert stats.shard_retries == 0           # repair is not a shard retry
    assert stats.degraded == []


def test_all_copies_corrupt_is_typed_failure_and_revokes_mav():
    rng = np.random.default_rng(93)
    store = replicated_store(rng, k=2)
    db = Database(store)
    q = Query(group_by=("g",), aggs=(QAgg("sum", "v", "sv"),))
    db.create_mav("mv_g", MAVDefinition(
        group_by=("g",), aggs=(AggSpec("sum", "v", "sv"),)))
    assert db.explain(q).route == "mav"
    corrupt_block(store, "v", block=2)
    corrupt_replica(store, "v", block=2, replica=0)
    with pytest.raises(BlockCorruption) as ei:    # nothing left to heal from
        db.query(q, use_mv=False)
    assert ei.value.column == "v" and ei.value.block == 2
    assert store.has_quarantined_blocks()         # permanent quarantine
    assert db.explain(q).route != "mav"           # rewrite revoked
    sr = replica_set(store)
    assert sr.events[-1] == ("unrepairable v/block 2: "
                             "all 1 replica(s) corrupt")


def test_repair_preserves_mav_eligibility():
    rng = np.random.default_rng(94)
    store = replicated_store(rng, k=2)
    db = Database(store)
    q = Query(group_by=("g",), aggs=(QAgg("sum", "v", "sv"),))
    db.create_mav("mv_g", MAVDefinition(
        group_by=("g",), aggs=(AggSpec("sum", "v", "sv"),)))
    assert db.explain(q).route == "mav"
    corrupt_block(store, "v", block=0)
    rs = db.query(q, use_mv=False)            # the read repairs in place
    assert rs.plan.repaired == ["repaired v/block 0 from replica 0"]
    assert "repaired=[" in rs.plan.describe()
    assert db.explain(q).route == "mav"       # store clean: rewrite stays


def test_scrub_heals_replicas_from_primary():
    rng = np.random.default_rng(96)
    store = replicated_store(rng, k=2)
    sr = replica_set(store)
    assert sr is not None and sr.k == 2 and sr.nbytes() > 0
    corrupt_replica(store, "v", block=3, replica=0)
    assert sr.scrub() == [
        "scrub: re-cloned v/block 3 replica 0 from primary"]
    # the re-cloned replica is usable: corrupt the primary, the read heals
    corrupt_block(store, "v", block=3)
    _, stats = PushdownExecutor().execute_stats(store, GROUPED_Q)
    assert stats.repaired == ["repaired v/block 3 from replica 0"]
    assert sr.scrub() == []                   # store fully clean again


def test_replicas_reattach_on_new_baseline():
    rng = np.random.default_rng(97)
    store = replicated_store(rng, k=2, n=128)
    v0 = replica_set(store)
    assert v0 is not None
    for j in range(128, 160):
        store.insert({"k": j, "g": 1, "d": 100, "v": 1.0, "s": "beta"})
    store.major_compact()                     # new baseline version
    v1 = replica_set(store)
    assert v1 is not None and v1 is not v0
    assert v1.version == store.baseline.version


# ---------------------------------------------------------------------------
# circuit breakers: cross-query pre-degrade + half-open probes
# ---------------------------------------------------------------------------


def test_breaker_unit_lifecycle():
    br = Breaker("sharded", threshold=2, cooldown=2)
    assert br.consult() is None
    br.record_failure()
    assert br.state == "closed"               # below threshold
    br.record_failure()
    assert br.state == "open" and br.opened_total == 1
    assert br.consult(advance=False) == "skip"  # explain: no cool-down tick
    assert br.consult() == "skip"             # cool-down consult 1 of 2
    assert br.consult() == "probe"            # consult 2: half-open
    assert br.state == "half-open"
    br.record_failure()                       # probe failed: reopen
    assert br.state == "open" and br.opened_total == 2
    assert br.consult() == "skip" and br.consult() == "probe"
    br.record_success()                       # probe succeeded this time
    assert br.state == "closed" and br.consecutive_failures == 0


def test_breaker_open_pre_degrades_and_half_open_probe_restores():
    """Escalation lifecycle (PR 9): q1's shard failure opens only the
    shard breaker; q2's fail-fast attempt failing again proves the rung
    sick and opens the rung breaker; then the classic open → pre-degrade
    → half-open probe → closed choreography plays out."""
    rng = np.random.default_rng(98)
    db = Database(make_store(rng), max_workers=4)
    with inject(FaultPlan(fail_shard={1: 999})):
        r1 = db.query(GROUPED_Q, engine="sharded", n_shards=4)
        assert any(d.startswith("sharded->vectorized")
                   for d in r1.plan.degraded)
        assert any("breaker(sharded[1]): state=open" in l
                   for l in db.health_report())
        assert not any("breaker(sharded):" in l for l in db.health_report())
        # q2: the suspected shard fail-fasts (1 attempt), fails again →
        # the rung breaker opens too (the fan-out keeps collapsing)
        r2 = db.query(GROUPED_Q, engine="sharded", n_shards=4)
        assert r2.plan.degraded == [
            "breaker(sharded[1]) open: shard fail-fast (single attempt)",
            "sharded->vectorized: ShardFailure: shard 1 failed after "
            "1 attempt(s): RuntimeError('injected fault: shard 1 "
            "attempt 0')"]
    assert any("breaker(sharded): state=open" in l
               for l in db.health_report())
    # q3: rung breaker open (cool-down consult 1 of 2) → fan-out
    # pre-degraded without being attempted, even though the fault is gone
    r3 = db.query(GROUPED_Q, engine="sharded", n_shards=4)
    assert r3.plan.route == "pushdown"
    assert r3.plan.degraded == [
        "breaker(sharded) open: pre-degraded sharded->pushdown"]
    assert r3.stats.n_shards == 0             # the rung was never touched
    # q4: consult 2 expires the cool-down → half-open, this query probes
    # (the shard breaker reached half-open on the same consult ticks)
    r4 = db.query(GROUPED_Q, engine="sharded", n_shards=4)
    assert r4.plan.route == "sharded"
    assert r4.plan.degraded == [
        "breaker(sharded) half-open: attempting sharded fan-out",
        "breaker(sharded[1]) half-open: probing shard"]
    # probe succeeded: both breakers closed, q5 runs clean and silent
    r5 = db.query(GROUPED_Q, engine="sharded", n_shards=4)
    assert r5.plan.degraded == []
    assert any("breaker(sharded): state=closed" in l
               for l in db.health_report())
    assert any("breaker(sharded[1]): state=closed" in l
               for l in db.health_report())
    assert all(norm(r.rows) == norm(r1.rows) for r in (r2, r3, r4, r5))


def test_failed_probe_reopens_breaker():
    rng = np.random.default_rng(99)
    db = Database(make_store(rng), max_workers=4)
    with inject(FaultPlan(fail_shard={1: 999})):
        db.query(GROUPED_Q, engine="sharded", n_shards=4)   # shard opens
        db.query(GROUPED_Q, engine="sharded", n_shards=4)   # rung escalates
        db.query(GROUPED_Q, engine="sharded", n_shards=4)   # open: skip
        r4 = db.query(GROUPED_Q, engine="sharded", n_shards=4)  # probe fails
    assert any(d.startswith("sharded->vectorized") for d in r4.plan.degraded)
    rep = " ".join(db.health_report())
    assert "breaker(sharded): state=open" in rep and "opened_total=2" in rep
    r5 = db.query(GROUPED_Q, engine="sharded", n_shards=4)  # cooling again
    assert r5.plan.degraded == [
        "breaker(sharded) open: pre-degraded sharded->pushdown"]


def test_inconclusive_probe_leaves_breaker_half_open():
    rng = np.random.default_rng(102)
    db = Database(make_store(rng), max_workers=4)
    with inject(FaultPlan(fail_shard={1: 999})):
        db.query(GROUPED_Q, engine="sharded", n_shards=4)   # shard opens
        db.query(GROUPED_Q, engine="sharded", n_shards=4)   # rung escalates
    db.query(GROUPED_Q, engine="sharded", n_shards=4)       # open: skip
    # the cool-down expires on a query that can't exercise the rung: the
    # probe is inconclusive and the breaker stays half-open
    rp = db.query(GROUPED_Q, engine="pushdown")
    assert rp.plan.degraded == []
    assert any("breaker(sharded): state=half-open" in l
               for l in db.health_report())
    # the next sharded query is still the probe; its success closes it
    rs = db.query(GROUPED_Q, engine="sharded", n_shards=4)
    assert rs.plan.degraded == [
        "breaker(sharded) half-open: attempting sharded fan-out",
        "breaker(sharded[1]) half-open: probing shard"]
    assert any("breaker(sharded): state=closed" in l
               for l in db.health_report())


def test_explain_reports_breaker_without_advancing():
    rng = np.random.default_rng(103)
    db = Database(make_store(rng), max_workers=4)
    with inject(FaultPlan(fail_shard={1: 999})):
        db.query(GROUPED_Q, engine="sharded", n_shards=4)   # shard opens
        db.query(GROUPED_Q, engine="sharded", n_shards=4)   # rung escalates
    for _ in range(5):                        # explain never ticks cool-down
        p = db.explain(GROUPED_Q, engine="sharded", n_shards=4)
        assert p.route == "pushdown"
        assert p.degraded == [
            "breaker(sharded) open: pre-degraded sharded->pushdown"]
    assert any("state=open" in l for l in db.health_report())


def test_health_report_tracks_ewmas():
    rng = np.random.default_rng(104)
    db = Database(make_store(rng), max_workers=4)
    for _ in range(3):
        db.query(GROUPED_Q, engine="sharded", n_shards=4)
    rep = db.health_report()
    assert rep[0] == "queries=3"
    assert any(l.startswith("latency_ewma=") for l in rep)
    assert any(l.startswith("sharded: failure_ewma=0.00") for l in rep)
    assert not any("breaker" in l for l in rep)   # nothing ever opened
    assert Database(make_store(rng), health=False).health_report() == []


@pytest.mark.device
def test_collective_breaker_opens_pre_degrades_and_probe_restores():
    """The acceptance scenario: a persistently failing collective opens its
    breaker (after the in-route retry), the second query pre-degrades
    without touching the collective, and a half-open probe re-admits it."""
    rng = np.random.default_rng(101)
    store = make_store(rng, n=256, block_rows=64, dml=False)
    db = Database(store, max_workers=2)
    kw = dict(n_shards=2, device_route="collective")
    with inject(FaultPlan(fail_route_persistent=("collective",))) as fp:
        r1 = db.query(DEVICE_Q, **kw)
    assert any(d.startswith("device-collective->per-shard-device")
               for d in r1.plan.degraded)
    assert r1.stats.kernel_retries == 1       # in-route retry tried first
    assert [e.startswith("persistent kernel fault on 'collective'")
            for e in fp.events] == [True, True]
    assert any("breaker(device-collective): state=open" in l
               for l in db.health_report())
    # fault gone, but the breaker remembers: q2 never touches the collective
    r2 = db.query(DEVICE_Q, **kw)
    assert r2.plan.degraded == [
        "breaker(device-collective) open: pre-degraded to per-shard-device"]
    assert r2.stats.used_device and r2.stats.device_route == "host"
    # q3 is the half-open probe: collective re-attempted and re-admitted
    r3 = db.query(DEVICE_Q, **kw)
    assert r3.plan.degraded == [
        "breaker(device-collective) half-open: attempting collective route"]
    assert r3.stats.device_route == "collective"
    r4 = db.query(DEVICE_Q, **kw)
    assert r4.plan.degraded == []
    # routes differ in float-sum order: counts exact, sums to tolerance
    base = {r["g"]: r for r in r1.rows}
    for rs in (r2, r3, r4):
        got = {r["g"]: r for r in rs.rows}
        assert got.keys() == base.keys()
        for g in base:
            assert got[g]["n"] == base[g]["n"]
            np.testing.assert_allclose(got[g]["sv"], base[g]["sv"],
                                       atol=1e-3, rtol=1e-4)


# ---------------------------------------------------------------------------
# fault-plan hooks: per-route counters
# ---------------------------------------------------------------------------


def test_fail_route_counters_are_per_route():
    fp = FaultPlan(fail_route={"collective": 1})
    with pytest.raises(KernelLaunchError):
        fp.on_kernel_launch("collective")
    fp.on_kernel_launch("host")               # different route: unaffected
    fp.on_kernel_launch("collective")         # route call #2: succeeds
    assert fp.events == ["kernel fault on 'collective' route launch #1"]


def test_fail_route_persistent_never_stops_failing():
    fp = FaultPlan(fail_route_persistent=("collective",))
    for _ in range(3):
        with pytest.raises(KernelLaunchError):
            fp.on_kernel_launch("collective")
    fp.on_kernel_launch("host")
    assert len(fp.events) == 3
    assert all(e.startswith("persistent kernel fault") for e in fp.events)
