"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

pytestmark = pytest.mark.device     # interpret-mode kernel suite: one-flag
                                    # select/skip via -m device / -m "not device"

KEY = jax.random.PRNGKey(7)


def keys(n):
    return jax.random.split(KEY, n)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Hq,Hkv,S,D", [
    (1, 4, 4, 128, 64),     # MHA
    (2, 8, 2, 256, 64),     # GQA 4:1
    (1, 4, 1, 128, 128),    # MQA, wide head
    (2, 4, 2, 192, 32),     # non-power-of-two seq
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_vs_oracle(B, Hq, Hkv, S, D, causal, dtype):
    ks = keys(3)
    q = jax.random.normal(ks[0], (B, Hq, S, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    want = ref.ref_mha(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_custom_vjp_matches_naive_grads():
    ks = keys(3)
    B, Hq, Hkv, S, D = 2, 4, 2, 160, 32
    q = jax.random.normal(ks[0], (B, Hq, S, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, D))
    for causal in (True, False):
        f1 = lambda a, b, c: (ref.ref_flash(a, b, c, causal=causal,
                                            block_k=64) ** 2).sum()
        f2 = lambda a, b, c: (ref.ref_mha(a, b, c, causal=causal) ** 2).sum()
        g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# hybrid merge-on-read decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Hq,Hkv,Skv,D,blk", [
    (2, 8, 4, 256, 64, 64),
    (1, 4, 4, 128, 128, 128),
    (2, 4, 1, 512, 64, 128),
])
def test_hybrid_decode_kernel_vs_oracle(B, Hq, Hkv, Skv, D, blk):
    ks = keys(6)
    nb = Skv // blk
    k = jax.random.normal(ks[0], (B, Hkv, Skv, D))
    v = jax.random.normal(ks[1], (B, Hkv, Skv, D))
    kq, ksc = ops.quantize_kv_blocks(k, blk)
    vq, vsc = ops.quantize_kv_blocks(v, blk)
    q = jax.random.normal(ks[2], (B, Hq, D))
    valid = jnp.arange(nb)[None] < jnp.asarray(
        [[nb]] if B == 1 else [[nb], [max(nb // 2, 1)]])
    Tl = 32
    tk = jax.random.normal(ks[3], (B, Hkv, Tl, D))
    tv = jax.random.normal(ks[4], (B, Hkv, Tl, D))
    tl = jnp.asarray([7] if B == 1 else [7, 19])
    out = ops.hybrid_decode(q, kq, vq, ksc, vsc, valid, tk, tv, tl)
    want = ref.ref_hybrid_decode(q, kq, vq, ksc, vsc, valid, tk, tv, tl)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_hybrid_decode_zone_map_prune_is_conservative():
    """skip_eps>0 drops only blocks that cannot matter: output stays close
    to exact when the pruned blocks' bounds are far below the max.

    The hot block must be hot in *score*, not just in norm (the sketch
    bounds |score|): q and the planted block live in the positive orthant
    so q·k is genuinely large there."""
    ks = keys(6)
    B, Hq, Hkv, Skv, D, blk = 1, 4, 2, 512, 64, 64
    k = jax.random.normal(ks[0], (B, Hkv, Skv, D)) * 0.05
    k = k.at[:, :, 64:128].set(
        jnp.abs(jax.random.normal(ks[5], (B, Hkv, 64, D))) * 3.0)
    v = jax.random.normal(ks[1], (B, Hkv, Skv, D))
    kq, ksc = ops.quantize_kv_blocks(k, blk)
    vq, vsc = ops.quantize_kv_blocks(v, blk)
    q = jnp.abs(jax.random.normal(ks[2], (B, Hq, D)))
    valid = jnp.ones((B, Skv // blk), bool)
    tk = jnp.zeros((B, Hkv, 16, D)); tv = jnp.zeros((B, Hkv, 16, D))
    tl = jnp.zeros((B,), jnp.int32)
    sketches = ref.ref_block_sketch(k, blk)
    exact = ops.hybrid_decode(q, kq, vq, ksc, vsc, valid, tk, tv, tl)
    pruned = ops.hybrid_decode(q, kq, vq, ksc, vsc, valid, tk, tv, tl,
                               sketches, skip_eps=1e-6)
    np.testing.assert_allclose(pruned, exact, atol=5e-3, rtol=5e-3)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,h,dh,n,chunk", [
    (2, 128, 4, 16, 16, 32),
    (1, 256, 2, 32, 64, 64),
    (2, 64, 8, 8, 8, 16),
])
def test_ssd_kernel_vs_sequential_oracle(B, S, h, dh, n, chunk):
    ks = keys(6)
    x = jax.random.normal(ks[0], (B, S, h, dh))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, n))
    Cm = jax.random.normal(ks[4], (B, S, n))
    D = jnp.ones((h,))
    out = ops.ssd_scan(x, dt, A, Bm, Cm, D, chunk=chunk)
    want = ref.ref_ssd(x, dt, A, Bm, Cm, D_skip=D)
    np.testing.assert_allclose(out, want, atol=2e-4, rtol=2e-3)


def test_ssd_chunked_equals_sequential():
    ks = keys(5)
    B, S, h, dh, n = 2, 96, 3, 8, 12
    x = jax.random.normal(ks[0], (B, S, h, dh))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, n))
    Cm = jax.random.normal(ks[4], (B, S, n))
    got = ref.ref_ssd_chunked(x, dt, A, Bm, Cm, chunk=32)
    want = ref.ref_ssd(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-3)


# ---------------------------------------------------------------------------
# columnar scan / dict group-by
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nb,rows", [(4, 128), (8, 256), (1, 128)])
def test_columnar_scan_kernel(nb, rows):
    ks = keys(4)
    deltas = jax.random.randint(ks[0], (nb, rows), 0, 50, jnp.int32)
    bases = jax.random.randint(ks[1], (nb,), 0, 500, jnp.int32)
    counts = jnp.full((nb,), rows, jnp.int32).at[-1].set(rows // 2)
    vals = jax.random.normal(ks[2], (nb, rows))
    for lo, hi in ((100, 400), (0, 1000), (480, 481)):
        out = ops.columnar_scan(deltas, bases, counts,
                                jnp.int32(lo), jnp.int32(hi), vals)
        want = ref.ref_columnar_scan(deltas, bases, counts,
                                     jnp.int32(lo), jnp.int32(hi), vals)
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(want[0]))
        np.testing.assert_allclose(np.asarray(out[1]), np.asarray(want[1]),
                                   atol=1e-4, rtol=1e-5)
        if int(out[0]) > 0:   # empty selection: min/max sentinels
            # (±1e30 kernel vs ±inf ref) are semantically equal
            for a, b in zip(out[2:], want[2:]):
                np.testing.assert_allclose(np.asarray(a, np.float32),
                                           np.asarray(b, np.float32),
                                           atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("nb,rows,ndv", [
    (4, 128, 8), (8, 256, 16), (1, 128, 130),
    (2, 1024, 200),        # G·Bk > ONEHOT_ELEMS: two row chunks per block
    (2, 1536, 200),        # three chunks, the partial block ends mid-chunk
    (3, 200, 8)])          # Bk not a multiple of 128: lane padding
def test_fused_scan_agg_kernel_vs_oracle(nb, rows, ndv):
    ks = keys(5)
    deltas = jax.random.randint(ks[0], (nb, rows), 0, 50, jnp.int32)
    bases = jax.random.randint(ks[1], (nb,), 0, 500, jnp.int32)
    counts = jnp.full((nb,), rows, jnp.int32).at[-1].set(rows // 2)
    codes = jax.random.randint(ks[2], (nb, rows), 0, ndv, jnp.int32)
    vals = jax.random.normal(ks[3], (nb, rows))
    for lo, hi in ((100, 400), (0, 1000), (480, 481)):
        got = ops.fused_scan_agg(deltas, bases, counts, jnp.int32(lo),
                                 jnp.int32(hi), codes, vals, ndv=ndv)
        want = ref.ref_fused_scan_agg(deltas, bases, counts, jnp.int32(lo),
                                      jnp.int32(hi), codes, vals, ndv)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   atol=1e-4, rtol=1e-5)
        sel = np.asarray(got[0]) > 0          # empty groups: ±1e30 vs ±inf
        for a, b in zip(got[2:], want[2:]):
            np.testing.assert_allclose(np.asarray(a)[sel], np.asarray(b)[sel],
                                       atol=1e-4, rtol=1e-5)


def test_fused_scan_agg_kernel_vs_host_groupby():
    """Interpret-mode equivalence against the host VectorEngine._groupby
    reference: same BETWEEN filter + grouped count/sum/min/max."""
    from repro.core.engine import QAgg, Query, VectorEngine
    from repro.core.relation import ColType, Predicate, PredOp, Table, schema
    rng = np.random.default_rng(23)
    nb, rows, ndv = 4, 128, 12
    n = nb * rows
    day = rng.integers(0, 365, n)
    g = rng.integers(0, ndv, n)
    v = rng.normal(size=n)
    lo, hi = 100, 200
    # host reference: VectorEngine group-by over the filtered table
    t = Table.from_columns(
        schema(("g", ColType.INT), ("day", ColType.INT),
               ("v", ColType.FLOAT)),
        {"g": g, "day": day, "v": v})
    q = Query(preds=(Predicate("day", PredOp.BETWEEN, lo, hi),),
              group_by=("g",),
              aggs=(QAgg("count", None, "n"), QAgg("sum", "v", "sv"),
                    QAgg("min", "v", "mn"), QAgg("max", "v", "mx")))
    host = {r["g"]: r for r in VectorEngine().execute(t, q)}
    # device: FOR-encode day per block (base = block min), fused kernel
    dayb = day.reshape(nb, rows)
    bases = dayb.min(axis=1).astype(np.int32)
    deltas = (dayb - bases[:, None]).astype(np.int32)
    counts = np.full((nb,), rows, np.int32)
    cnt, sm, mn, mx = ops.fused_scan_agg(
        jnp.asarray(deltas), jnp.asarray(bases), jnp.asarray(counts),
        jnp.int32(lo), jnp.int32(hi), jnp.asarray(g.reshape(nb, rows),
                                                  dtype=jnp.int32),
        jnp.asarray(v.reshape(nb, rows), jnp.float32), ndv=ndv)
    cnt = np.asarray(cnt)
    for code in range(ndv):
        if code not in host:
            assert cnt[code] == 0
            continue
        assert int(cnt[code]) == host[code]["n"]
        np.testing.assert_allclose(float(np.asarray(sm)[code]),
                                   host[code]["sv"], atol=1e-3, rtol=1e-4)
        np.testing.assert_allclose(float(np.asarray(mn)[code]),
                                   host[code]["mn"], atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(float(np.asarray(mx)[code]),
                                   host[code]["mx"], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("nb,rows,ndvs,nvals", [
    (4, 128, (4, 3), 2),        # two int keys, two value columns
    (2, 128, (5,), 3),          # one key, three value columns
    (3, 256, (3, 2, 2), 1),     # three keys packed
])
def test_fused_scan_agg_multikey_multivalue_vs_oracle(nb, rows, ndvs, nvals):
    """Packed multi-key group codes + multiple value planes per pass."""
    ks = keys(4)
    deltas = jax.random.randint(ks[0], (nb, rows), 0, 50, jnp.int32)
    bases = jax.random.randint(ks[1], (nb,), 0, 500, jnp.int32)
    counts = jnp.full((nb,), rows, jnp.int32).at[-1].set(rows // 2)
    codes = jnp.stack([jax.random.randint(jax.random.fold_in(ks[2], k),
                                          (nb, rows), 0, d, jnp.int32)
                       for k, d in enumerate(ndvs)], axis=1)
    vals = jax.random.normal(ks[3], (nb, nvals, rows))
    for lo, hi in ((100, 400), (0, 1000), (480, 481)):
        got = ops.fused_scan_agg(deltas, bases, counts, jnp.int32(lo),
                                 jnp.int32(hi), codes, vals, ndv=ndvs)
        want = ref.ref_fused_scan_agg(deltas, bases, counts, jnp.int32(lo),
                                      jnp.int32(hi), codes, vals, ndvs)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   atol=1e-4, rtol=1e-5)
        sel = np.asarray(got[0]) > 0          # empty groups: ±1e30 vs ±inf
        for a, b in zip(got[2:], want[2:]):
            np.testing.assert_allclose(np.asarray(a)[:, sel],
                                       np.asarray(b)[:, sel],
                                       atol=1e-4, rtol=1e-5)


def test_fused_scan_agg_string_dict_key_vs_host_groupby():
    """A string dictionary group key (global dict codes) + int key, against
    the host VectorEngine over the decoded strings — the q2-style
    no-predicate group-by shape (all-zero deltas, lo = hi = 0)."""
    from repro.core.engine import QAgg, Query, VectorEngine
    from repro.core.relation import ColType, Table, schema
    rng = np.random.default_rng(29)
    nb, rows = 2, 128
    n = nb * rows
    words = np.asarray([b"alpha", b"beta", b"gamma"])
    s_codes = rng.integers(0, len(words), n)
    g = rng.integers(0, 4, n)
    v = rng.normal(size=n)
    w = rng.normal(size=n)
    t = Table.from_columns(
        schema(("g", ColType.INT), ("s", ColType.STR), ("v", ColType.FLOAT),
               ("w", ColType.FLOAT)),
        {"g": g, "s": words[s_codes], "v": v, "w": w})
    q = Query(group_by=("g", "s"),
              aggs=(QAgg("count", None, "n"), QAgg("sum", "v", "sv"),
                    QAgg("max", "w", "mw")))
    host = {(r["g"], r["s"]): r for r in VectorEngine().execute(t, q)}
    codes = np.stack([g.reshape(nb, rows), s_codes.reshape(nb, rows)],
                     axis=1).astype(np.int32)
    vals = np.stack([v.reshape(nb, rows), w.reshape(nb, rows)],
                    axis=1).astype(np.float32)
    zeros = jnp.zeros((nb, rows), jnp.int32)
    cnt, sums, mins, maxs = ops.fused_scan_agg(
        zeros, jnp.zeros((nb,), jnp.int32), jnp.full((nb,), rows, jnp.int32),
        jnp.int32(0), jnp.int32(0), jnp.asarray(codes), jnp.asarray(vals),
        ndv=(4, len(words)))
    cnt = np.asarray(cnt)
    for gi in range(4):
        for si, word in enumerate(words):
            p = gi * len(words) + si
            key = (gi, bytes(word))
            if key not in host:
                assert cnt[p] == 0
                continue
            assert int(cnt[p]) == host[key]["n"]
            np.testing.assert_allclose(float(np.asarray(sums)[0, p]),
                                       host[key]["sv"], atol=1e-3, rtol=1e-4)
            np.testing.assert_allclose(float(np.asarray(maxs)[1, p]),
                                       host[key]["mw"], atol=1e-5, rtol=1e-5)


def test_fused_scan_agg_block_mask_prunes():
    """Zone-map survivors only: masked blocks contribute nothing."""
    ks = keys(4)
    nb, rows, ndv = 6, 128, 8
    deltas = jax.random.randint(ks[0], (nb, rows), 0, 50, jnp.int32)
    bases = jnp.zeros((nb,), jnp.int32)
    counts = jnp.full((nb,), rows, jnp.int32)
    codes = jax.random.randint(ks[1], (nb, rows), 0, ndv, jnp.int32)
    vals = jax.random.normal(ks[2], (nb, rows))
    mask = jnp.asarray([True, False, True, False, False, True])
    got = ops.fused_scan_agg(deltas, bases, counts, jnp.int32(0),
                             jnp.int32(100), codes, vals, ndv=ndv,
                             block_mask=mask)
    want = ref.ref_fused_scan_agg(deltas, bases, counts, jnp.int32(0),
                                  jnp.int32(100), codes, vals, ndv,
                                  block_mask=mask)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("factor", [2, 3, 4, 8])
def test_fused_scan_agg_coalesced_tiles_identical(factor):
    """Selectivity-matched tile shapes: fusing adjacent blocks into one
    kernel tile (rebased FOR deltas, member-major code/value planes, padded
    tail, partial last block) returns bit-equal counts and tolerance-equal
    sums/extrema for any factor, including a zone-map-consistently pruned
    member merged into a surviving tile."""
    rng = np.random.default_rng(0)
    nb, bk, ndv = 7, 64, (5, 3)
    deltas = rng.integers(0, 500, (nb, bk)).astype(np.int32)
    bases = rng.integers(-100, 100, nb).astype(np.int32)
    counts = np.full(nb, bk, np.int32)
    counts[-1] = 17                      # partial globally-last block
    codes = np.stack([rng.integers(0, d, (nb, bk)) for d in ndv],
                     1).astype(np.int32)
    values = rng.normal(size=(nb, 2, bk)).astype(np.float32)
    deltas[2] += 10_000                  # block 2 entirely above the window
    mask = np.ones(nb, bool)
    mask[2] = False                      # ...so pruning it is zone-map-exact
    lo, hi = np.int32(40), np.int32(400)
    want = [np.asarray(x) for x in ref.ref_fused_scan_agg(
        deltas, bases, counts, lo, hi, jnp.asarray(codes),
        jnp.asarray(values), ndv, jnp.asarray(mask))]
    got = [np.asarray(x) for x in ops.fused_scan_agg(
        deltas, bases, counts, lo, hi, codes, values, ndv=ndv,
        block_mask=mask, coalesce=factor)]
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_allclose(got[1], want[1], atol=1e-4, rtol=1e-5)
    sel = want[0] > 0
    for a, b in zip(got[2:], want[2:]):
        np.testing.assert_allclose(a[:, sel], b[:, sel], atol=1e-4,
                                   rtol=1e-5)


def test_fused_scan_agg_coalesce_legacy_layout():
    """coalesce composes with the legacy 2-D single-key layout (the V axis
    squeeze is preserved)."""
    rng = np.random.default_rng(1)
    nb, bk = 4, 32
    deltas = rng.integers(0, 300, (nb, bk)).astype(np.int32)
    bases = np.zeros(nb, np.int32)
    counts = np.full(nb, bk, np.int32)
    codes = rng.integers(0, 6, (nb, bk)).astype(np.int32)
    vals = rng.normal(size=(nb, bk)).astype(np.float32)
    want = [np.asarray(x) for x in ops.fused_scan_agg(
        deltas, bases, counts, np.int32(0), np.int32(200), codes, vals,
        ndv=6)]
    got = [np.asarray(x) for x in ops.fused_scan_agg(
        deltas, bases, counts, np.int32(0), np.int32(200), codes, vals,
        ndv=6, coalesce=2)]
    assert got[1].ndim == 1
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_allclose(got[1], want[1], atol=1e-4, rtol=1e-5)


def test_device_executor_launches_coalesced_tiles():
    """An unpruned full scan through PushdownExecutor(device=True) picks a
    >1-block kernel tile from the cost model and still matches the host."""
    from repro.core.engine import QAgg, Query
    from repro.core.lsm import LSMStore
    from repro.core.pushdown import PushdownExecutor
    from repro.core.relation import ColType, schema
    rng = np.random.default_rng(3)
    n, br = 1 << 14, 512
    store = LSMStore(schema(("k", ColType.INT), ("g", ColType.INT),
                            ("v", ColType.FLOAT)), block_rows=br)
    store.bulk_insert({"k": np.arange(n), "g": rng.integers(0, 5, n),
                       "v": rng.normal(size=n)})
    q = Query(group_by=("g",), aggs=(QAgg("count", None, "n"),
                                     QAgg("sum", "v", "sv")))
    host = {r["g"]: r for r in PushdownExecutor().execute(store, q)}
    dev, stats = PushdownExecutor(device=True).execute_stats(store, q)
    assert stats.used_device
    assert stats.device_tile_blocks > 1
    devm = {r["g"]: r for r in dev}
    assert host.keys() == devm.keys()
    for g in host:
        assert host[g]["n"] == devm[g]["n"]
        np.testing.assert_allclose(devm[g]["sv"], host[g]["sv"],
                                   atol=1e-3, rtol=1e-4)


@pytest.mark.parametrize("N,ndv", [(512, 8), (2048, 16), (1024, 128)])
def test_dict_groupby_kernel(N, ndv):
    ks = keys(2)
    codes = jax.random.randint(ks[0], (N,), 0, ndv, jnp.int32)
    vals = jax.random.normal(ks[1], (N,))
    sums, counts = ops.dict_groupby(codes, vals, ndv=ndv)
    wsums, wcounts = ref.ref_dict_groupby(codes, vals, ndv)
    np.testing.assert_allclose(sums, wsums, atol=1e-3, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(wcounts))


def test_kernel_dispatch_refuses_backends_without_a_kernel_path(monkeypatch):
    """Interpret mode is for the CPU only: any other non-TPU backend is an
    error, never a silent interpreter."""
    from repro.kernels.ops import _on_tpu
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no Pallas TPU kernel path"):
        _on_tpu()
