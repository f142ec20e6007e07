"""Hypothesis property tests for the core invariants (paper §III/§V).

Kept in their own module so the tier-1 suite still collects when
``hypothesis`` is absent (see requirements-dev.txt); the deterministic
versions of these invariants live in test_core.py / test_pushdown.py.
"""
import dataclasses

import numpy as np
import pytest

pytest.importorskip("hypothesis")

from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core.encoding import (clone_block, encode_column,
                                 payload_checksum)
from repro.core.lsm import LSMStore
from repro.core.relation import (ColType, Column, ColumnSpec, Predicate,
                                 PredOp, schema)
from repro.core.skipping import SkippingIndex, Verdict

SCH = schema(("k", ColType.INT), ("a", ColType.INT), ("b", ColType.FLOAT))


# ---------------------------------------------------------------------------
# LSM merge-on-read == replay oracle
# ---------------------------------------------------------------------------

ops_strategy = st.lists(
    st.tuples(
        st.sampled_from(["insert", "update", "delete", "minor", "major"]),
        st.integers(0, 19),            # key
        st.integers(-50, 50),          # value
    ),
    min_size=1, max_size=60)


@given(ops_strategy)
@settings(max_examples=60, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_lsm_merge_on_read_equals_oracle(ops):
    store = LSMStore(SCH, block_rows=8)
    oracle = {}
    for op, k, v in ops:
        if op == "insert":
            if k not in oracle:
                store.insert({"k": k, "a": v, "b": float(v) / 2})
                oracle[k] = (v, float(v) / 2)
        elif op == "update":
            if k in oracle:
                store.update(k, {"a": v})
                oracle[k] = (v, oracle[k][1])
        elif op == "delete":
            if k in oracle:
                store.delete(k)
                del oracle[k]
        elif op == "minor":
            store.freeze_memtable()
            store.minor_compact()
        else:
            store.major_compact()
    table, _ = store.scan()
    got = {int(r["k"]): (int(r["a"]), float(r["b"]))
           for r in table.rows()}
    assert got == oracle
    # point reads agree too
    for k in range(20):
        row = store.get(k)
        assert (row is None) == (k not in oracle)
        if row is not None:
            assert int(row["a"]) == oracle[k][0]


# ---------------------------------------------------------------------------
# encodings (round-trip + encoded-domain predicates)
# ---------------------------------------------------------------------------

int_cols = st.lists(st.integers(-1000, 1000), min_size=1, max_size=200)


@given(int_cols)
@settings(max_examples=60, deadline=None)
def test_int_encoding_roundtrip(vals):
    col = Column.from_values(ColumnSpec("x", ColType.INT), vals)
    enc = encode_column(col)
    np.testing.assert_array_equal(enc.decode(), col.values)


@given(int_cols, st.integers(-1000, 1000))
@settings(max_examples=40, deadline=None)
def test_encoded_domain_predicate_equals_decoded(vals, pivot):
    col = Column.from_values(ColumnSpec("x", ColType.INT), vals)
    enc = encode_column(col)
    for op in (PredOp.EQ, PredOp.LE, PredOp.GT):
        pred = Predicate("x", op, pivot)
        got = enc.eval_pred(pred)      # None = encoding can't answer (fine)
        if got is not None:
            np.testing.assert_array_equal(got, pred.eval(col))


@given(st.lists(st.sampled_from(["alpha", "alpine", "alps", "beta", "bet"]),
                min_size=1, max_size=100))
@settings(max_examples=40, deadline=None)
def test_str_encoding_roundtrip(vals):
    col = Column.from_values(ColumnSpec("s", ColType.STR), vals)
    enc = encode_column(col)
    np.testing.assert_array_equal(enc.decode(), col.values)


# ---------------------------------------------------------------------------
# skipping index: conservative pruning + sketch aggregates
# ---------------------------------------------------------------------------


@given(st.lists(st.integers(-100, 100), min_size=8, max_size=300),
       st.integers(-100, 100), st.integers(-100, 100))
@settings(max_examples=60, deadline=None)
def test_skipping_index_no_false_negatives(vals, lo, hi):
    lo, hi = min(lo, hi), max(lo, hi)
    arr = np.asarray(vals, np.int64)
    idx = SkippingIndex.build(arr, block_rows=16)
    pred = Predicate("x", PredOp.BETWEEN, lo, hi)
    verdicts = idx.prune(pred)
    for b in range(len(verdicts)):
        blk = arr[b * 16:(b + 1) * 16]
        match = (blk >= lo) & (blk <= hi)
        if verdicts[b] == Verdict.NONE.value:
            assert not match.any()     # pruning must be conservative
        if verdicts[b] == Verdict.ALL.value:
            assert match.all()


# ---------------------------------------------------------------------------
# payload checksums: clone round-trip + bit-flip detection (replica repair)
# ---------------------------------------------------------------------------

str_vals = st.lists(st.sampled_from(["alpha", "alpine", "alps", "beta"]),
                    min_size=1, max_size=100)


@given(st.one_of(int_cols.map(lambda v: (ColType.INT, v)),
                 str_vals.map(lambda v: (ColType.STR, v))))
@settings(max_examples=60, deadline=None)
def test_payload_checksum_clone_roundtrip(tv):
    ctype, vals = tv
    enc = encode_column(Column.from_values(ColumnSpec("x", ctype), vals))
    c0 = payload_checksum(enc)
    clone = clone_block(enc)
    assert payload_checksum(clone) == c0      # clones are bit-identical
    assert payload_checksum(enc) == c0        # and checksumming is pure
    np.testing.assert_array_equal(clone.decode(), enc.decode())


@given(int_cols, st.data())
@settings(max_examples=60, deadline=None)
def test_payload_checksum_detects_any_single_bit_flip(vals, data):
    enc = clone_block(encode_column(
        Column.from_values(ColumnSpec("x", ColType.INT), vals)))
    c0 = payload_checksum(enc)
    arrays = [(f.name, getattr(enc, f.name))
              for f in dataclasses.fields(enc)
              if isinstance(getattr(enc, f.name), np.ndarray)
              and getattr(enc, f.name).size]
    name, v = data.draw(st.sampled_from(arrays))
    w = np.ascontiguousarray(v).copy()
    raw = w.view(np.uint8).reshape(-1)
    i = data.draw(st.integers(0, raw.size - 1))
    raw[i] ^= np.uint8(1 << data.draw(st.integers(0, 7)))
    setattr(enc, name, w)
    assert payload_checksum(enc) != c0  # CRC32 catches every 1-bit error


@given(st.lists(st.integers(-100, 100), min_size=8, max_size=300))
@settings(max_examples=40, deadline=None)
def test_sketch_aggregates_match_exact(vals):
    arr = np.asarray(vals, np.int64)
    idx = SkippingIndex.build(arr, block_rows=16)
    assert idx.try_aggregate("min") == arr.min()
    assert idx.try_aggregate("max") == arr.max()
    assert idx.try_aggregate("sum") == arr.sum()
    assert idx.try_aggregate("count_star") == len(arr)


# ---------------------------------------------------------------------------
# WAL framing (core/wal.py)
# ---------------------------------------------------------------------------

wal_record_strategy = st.builds(
    lambda kind, seq, ts, gen, data: (kind, seq, ts, gen, data),
    st.sampled_from(["insert", "update", "delete", "purge", "major_compact"]),
    st.integers(1, 2**31),
    st.integers(0, 2**31),
    st.integers(0, 64),
    st.dictionaries(
        st.sampled_from(["pk", "row", "ts", "version"]),
        st.one_of(st.integers(-2**31, 2**31), st.floats(allow_nan=False),
                  st.text(max_size=20), st.none()),
        max_size=4))


@given(st.lists(wal_record_strategy, min_size=1, max_size=12))
@settings(max_examples=50, deadline=None)
def test_wal_encode_decode_roundtrip(recs):
    from repro.core.wal import WalRecord, decode_record, encode_record
    for kind, seq, ts, gen, data in recs:
        rec = WalRecord(kind, seq, ts, gen, data)
        out = decode_record(encode_record(rec))
        assert (out.kind, out.seq, out.ts, out.gen, out.data) == \
            (kind, seq, ts, gen, data)


@given(recs=st.lists(wal_record_strategy, min_size=1, max_size=8),
       data=st.data())
@settings(max_examples=60, deadline=None)
def test_wal_single_bit_flip_never_silently_decodes(recs, data,
                                                    tmp_path_factory):
    """Flip one bit anywhere in the log: scanning must either raise a typed
    RecoveryError or exclude the damaged record (a flip in a length field
    can make the tail read as torn) — it may never yield a record whose
    payload differs from what was written."""
    from repro.core.errors import RecoveryError
    from repro.core.wal import WalRecord, encode_record, scan_wal
    frames = [encode_record(WalRecord(*r)) for r in recs]
    buf = bytearray(b"".join(frames))
    i = data.draw(st.integers(0, len(buf) - 1))
    bit = data.draw(st.integers(0, 7))
    buf[i] ^= 1 << bit
    path = str(tmp_path_factory.mktemp("flip") / "flip.wal")  # per example
    with open(path, "wb") as f:
        f.write(bytes(buf))
    want = [(r[0], r[1], r[2], r[3], r[4]) for r in recs]
    try:
        got, torn, _ = scan_wal(path)
    except RecoveryError:
        return                                     # typed failure: fine
    # decoded records must be a prefix of what was written, with the
    # damaged record (and everything after it) excluded, never mutated
    decoded = [(g.kind, g.seq, g.ts, g.gen, g.data) for g in got]
    assert decoded == want[:len(decoded)]
    assert len(decoded) < len(want) or not torn


@given(recs=st.lists(wal_record_strategy, min_size=1, max_size=8),
       data=st.data())
@settings(max_examples=60, deadline=None)
def test_wal_torn_tail_yields_longest_valid_prefix(recs, data,
                                                   tmp_path_factory):
    """Truncate the log at any byte offset: scan_wal returns exactly the
    records whose complete frames fit in the prefix, flags the tail torn
    iff bytes of an incomplete frame remain, and reports the resume
    offset at the end of the last complete frame."""
    from repro.core.wal import WalRecord, encode_record, scan_wal
    frames = [encode_record(WalRecord(*r)) for r in recs]
    whole = b"".join(frames)
    cut = data.draw(st.integers(0, len(whole)))
    path = str(tmp_path_factory.mktemp("torn") / "torn.wal")  # per example
    with open(path, "wb") as f:
        f.write(whole[:cut])
    got, torn, valid = scan_wal(path)

    n, off = 0, 0
    while n < len(recs) and off + len(frames[n]) <= cut:
        off += len(frames[n])
        n += 1
    assert len(got) == n
    assert valid == off
    assert torn == (cut > off)
    for g, r in zip(got, recs):
        assert (g.kind, g.seq, g.ts, g.gen, g.data) == r
