"""Fused filter + *grouped* aggregation over encoded blocks (Pallas TPU).

Extends ``columnar_scan`` (flat count/sum/min/max) to grouped aggregation
over dictionary codes, covering the ``bench_vectorized`` q1/q2/q3 shapes
end-to-end on device: an optional BETWEEN predicate evaluated in the
FOR/delta encoded domain (bounds shifted into each block's offset domain —
query without decompression), then per-group count/sum/min/max accumulated
in one pass.

Group keys are **multi-key**: each block carries ``K`` int32 code planes
(one per group-by column — int columns use their global value dictionary,
string columns their global string dictionary), and the kernel packs them
into a single radix code ``sum_k codes[k] * stride[k]`` on device — the
sequence-preserving encoding of ``engine.pack_sort_keys``, executed on the
VPU so multi-column group-bys cost one one-hot contraction, not K.

Layout is lane-major: a block's rows lie on the 128-wide lanes, so every
per-row plane is a ``[1, C]`` or ``[K|V, C]`` slab and nothing is ever
broadcast across lanes.  Each grid step takes one ``C``-row chunk of one
block and builds the one-hot ``hit [G, C]`` (G = prod(ndv) padded to a
sublane multiple) by comparing a sublane iota with the packed keys.  Sums
are one ``[G, C] x [V, C]^T`` MXU contraction at HIGHEST precision (the
one-hot is exact in any precision, the values are f32); counts, mins and
maxs reduce ``hit`` along the lanes.  The four accumulators (count i32
``[G, 1]``, sum/min/max f32 ``[G, V]``) are the kernel's outputs, resident
in VMEM across the whole grid.  ``C`` is the largest 128-multiple divisor
of the block height that keeps the one-hot under ``ONEHOT_ELEMS``.

The zone-map skip uses the scalar-prefetch visit-list trick: the wrapper
prunes blocks with the skipping index and the kernel only ever DMAs the
surviving blocks; per-block FOR bases and row counts ride in SMEM beside
the visit list.  A query with no predicate passes all-zero deltas/bases
with lo = hi = 0, selecting every valid row.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

POS_INF = 1e30
LANES = 128
SUBLANES = 8
ONEHOT_ELEMS = 1 << 17        # one-hot chunk budget: G * C f32 = 512 KiB


def coalesce_blocks(deltas: jax.Array, bases: jax.Array, counts: jax.Array,
                    codes: jax.Array, values: jax.Array,
                    block_mask: jax.Array, factor: int):
    """Fuse ``factor`` adjacent staged blocks into one kernel tile, so the
    grid launches with selectivity-matched tile shapes (the cost model picks
    ``factor``: large tiles for full scans amortize grid steps, factor 1
    keeps the visit-list prune block-granular for selective scans).

    FOR deltas are rebased onto the tile-wide minimum base (exact: the
    executor stages only columns within ±2^30, so the rebased offsets stay
    inside int32), code/value planes are re-laid out member-major, counts
    add, and a tile survives the zone-map prune if any member does (pruned
    members inside a surviving tile are re-filtered exactly by the kernel's
    predicate window, costing only wasted lanes, never wrong rows).

    Precondition: within a tile, every member after a partially-filled
    member must be empty — the baseline layout (only the globally-last
    block is partial) and trailing zero-count padding both satisfy it, so
    valid rows stay a prefix and the kernel's ``row < count`` check
    carries over.

    Expects the general layout (deltas [Nb, Bk], codes [Nb, K, Bk], values
    [Nb, V, Bk]) and returns it with ``Bk * factor`` rows per tile."""
    nb, bk = deltas.shape
    f = max(int(factor), 1)
    nb2 = -(-nb // f)
    pad = nb2 * f - nb
    if pad:
        deltas = jnp.pad(deltas, ((0, pad), (0, 0)))
        bases = jnp.pad(bases, (0, pad))
        counts = jnp.pad(counts, (0, pad))
        codes = jnp.pad(codes, ((0, pad), (0, 0), (0, 0)))
        values = jnp.pad(values, ((0, pad), (0, 0), (0, 0)))
        block_mask = jnp.pad(block_mask, (0, pad))
    k, v = codes.shape[1], values.shape[1]
    b2 = bases.reshape(nb2, f).astype(jnp.int32)
    base2 = b2.min(axis=1)
    shift = b2 - base2[:, None]
    deltas2 = (deltas.astype(jnp.int32).reshape(nb2, f, bk)
               + shift[:, :, None]).reshape(nb2, f * bk)
    counts2 = counts.reshape(nb2, f).sum(axis=1).astype(jnp.int32)
    codes2 = (codes.reshape(nb2, f, k, bk).transpose(0, 2, 1, 3)
              .reshape(nb2, k, f * bk))
    values2 = (values.reshape(nb2, f, v, bk).transpose(0, 2, 1, 3)
               .reshape(nb2, v, f * bk))
    mask2 = block_mask.reshape(nb2, f).any(axis=1)
    return deltas2, base2, counts2, codes2, values2, mask2


def _fused_kernel(bids_ref, cnt_ref, bases_ref, counts_ref, bounds_ref,
                  deltas_ref, codes_ref, values_ref,
                  cnt_out, sum_out, min_out, max_out, *,
                  chunk: int, strides: Tuple[int, ...]):
    j, c = pl.program_id(0), pl.program_id(1)
    g, n_vals = sum_out.shape

    @pl.when((j == 0) & (c == 0))
    def _init():
        cnt_out[...] = jnp.zeros_like(cnt_out)
        sum_out[...] = jnp.zeros_like(sum_out)
        min_out[...] = jnp.full_like(min_out, POS_INF)
        max_out[...] = jnp.full_like(max_out, -POS_INF)

    @pl.when(j < cnt_ref[0])
    def _body():
        b = bids_ref[j]
        base = bases_ref[b]
        lo = bounds_ref[0] - base                         # encoded-domain
        hi = bounds_ref[1] - base                         # bounds
        deltas = deltas_ref[0]                            # [1, C]
        # device-side pack_sort_keys: radix-pack the K code planes
        packed = codes_ref[0, 0:1, :] * strides[0]
        for k in range(1, len(strides)):
            packed = packed + codes_ref[0, k:k + 1, :] * strides[k]
        row = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) + c * chunk
        sel = (deltas >= lo) & (deltas <= hi) & (row < counts_ref[b])
        key = jnp.where(sel, packed, -1)                  # -1: no group
        hit = jax.lax.broadcasted_iota(jnp.int32, (g, chunk), 0) == key
        onehot = hit.astype(jnp.float32)                  # [G, C]
        vals = values_ref[0]                              # [V, C] f32
        cnt_out[...] += jnp.sum(onehot, axis=1,
                                keepdims=True).astype(jnp.int32)
        sum_out[...] += jax.lax.dot_general(
            onehot, vals, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        col = jax.lax.broadcasted_iota(jnp.int32, (g, n_vals), 1)
        mins, maxs = min_out[...], max_out[...]
        for v in range(n_vals):
            vrow = values_ref[0, v:v + 1, :]              # [1, C]
            mn = jnp.min(jnp.where(hit, vrow, POS_INF), axis=1, keepdims=True)
            mx = jnp.max(jnp.where(hit, vrow, -POS_INF), axis=1,
                         keepdims=True)
            mins = jnp.where(col == v, jnp.minimum(mins, mn), mins)
            maxs = jnp.where(col == v, jnp.maximum(maxs, mx), maxs)
        min_out[...] = mins
        max_out[...] = maxs


def _chunk_rows(bk: int, g: int) -> int:
    """Rows per grid step: the largest 128-multiple divisor of ``bk`` whose
    ``[g, C]`` one-hot stays within ``ONEHOT_ELEMS`` (at least 128)."""
    best = LANES
    for c in range(LANES, bk + 1, LANES):
        if bk % c == 0 and g * c <= ONEHOT_ELEMS:
            best = c
    return best


def _normalize(codes: jax.Array, values: jax.Array,
               ndv: Union[int, Sequence[int]]):
    """Accept the legacy single-key/single-value layout ([Nb, Bk] + int ndv)
    alongside the general [Nb, K, Bk] / [Nb, V, Bk] + tuple-ndv one."""
    legacy = codes.ndim == 2 and values.ndim == 2 and not isinstance(
        ndv, (tuple, list))
    codes3 = codes[:, None, :] if codes.ndim == 2 else codes
    values3 = values[:, None, :] if values.ndim == 2 else values
    ndv_t = ((int(ndv),) if not isinstance(ndv, (tuple, list))
             else tuple(int(x) for x in ndv))
    if len(ndv_t) != codes3.shape[1]:
        raise ValueError(f"ndv {ndv_t} does not match {codes3.shape[1]} "
                         "group-key code planes")
    strides = []
    acc = 1
    for d in reversed(ndv_t):
        strides.append(acc)
        acc *= d
    return legacy, codes3, values3, ndv_t, tuple(reversed(strides)), acc


def fused_scan_agg(deltas: jax.Array, bases: jax.Array, counts: jax.Array,
                   lo, hi, codes: jax.Array, values: jax.Array,
                   ndv: Union[int, Sequence[int]],
                   block_mask: Optional[jax.Array] = None,
                   *, interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """deltas: [Nb, Bk] int32 FOR offsets of the filter column (all-zero with
    lo = hi = 0 for predicate-less group-bys); bases/counts: [Nb]; lo/hi:
    scalars in the *decoded* domain; codes: [Nb, Bk] or [Nb, K, Bk] int32
    global group codes, plane k in [0, ndv[k]); values: [Nb, Bk] or
    [Nb, V, Bk] f32 aggregation targets; block_mask: [Nb] bool zone-map
    survivors.  Returns per-packed-group (count i32 [P], sum f32 [V, P],
    min f32, max f32) with P = prod(ndv); with the legacy 2-D layout the
    V axis is squeezed.  Empty groups report count 0, sum 0, min +POS_INF,
    max -POS_INF."""
    Nb, Bk = deltas.shape
    legacy, codes3, values3, ndv_t, strides, P = _normalize(codes, values, ndv)
    K, V = codes3.shape[1], values3.shape[1]
    G = -(-P // SUBLANES) * SUBLANES
    Bkp = -(-Bk // LANES) * LANES         # rows padded onto whole lanes; the
    if Bkp != Bk:                         # row < count check masks the pad
        pad = ((0, 0), (0, 0), (0, Bkp - Bk))
        deltas = jnp.pad(deltas, pad[1:])
        codes3, values3 = jnp.pad(codes3, pad), jnp.pad(values3, pad)
    C = _chunk_rows(Bkp, G)
    if block_mask is None:
        block_mask = jnp.ones((Nb,), bool)
    order = jnp.argsort(~block_mask, stable=True)
    cnt = block_mask.sum().astype(jnp.int32)
    idx = jnp.minimum(jnp.arange(Nb), jnp.maximum(cnt - 1, 0))
    bids = jnp.take_along_axis(order, idx, axis=0).astype(jnp.int32)
    bounds = jnp.stack([jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32)])

    def rows(n):                          # block bids[j], chunk c
        return pl.BlockSpec((1, n, C), lambda j, c, bids, *_: (bids[j], 0, c))

    def whole(shape):
        return pl.BlockSpec(shape, lambda j, c, *_: (0, 0))

    kernel = functools.partial(_fused_kernel, chunk=C, strides=strides)
    g_cnt, sums, mins, maxs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(Nb, Bkp // C),
            in_specs=[rows(1), rows(K), rows(V)],
            out_specs=[whole((G, 1)), whole((G, V)), whole((G, V)),
                       whole((G, V))],
        ),
        out_shape=[jax.ShapeDtypeStruct((G, 1), jnp.int32)]
        + [jax.ShapeDtypeStruct((G, V), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(bids, cnt[None], bases.astype(jnp.int32), counts.astype(jnp.int32),
      bounds, deltas.astype(jnp.int32).reshape(Nb, 1, Bkp),
      codes3.astype(jnp.int32), values3.astype(jnp.float32))
    g_cnt = g_cnt[:P, 0]
    sums, mins, maxs = sums[:P].T, mins[:P].T, maxs[:P].T
    if legacy:
        return g_cnt, sums[0], mins[0], maxs[0]
    return g_cnt, sums, mins, maxs


def sharded_scan_agg(deltas: jax.Array, bases: jax.Array, counts: jax.Array,
                     lo, hi, codes: jax.Array, values: jax.Array,
                     ndv: Sequence[int], block_mask: jax.Array, mesh,
                     *, coalesce: int = 1, topk: int = 0,
                     interpret: bool = False):
    """Single-launch sharded fused scan-agg with an on-device collective
    tree-reduce (the distributed read path of the paper's §V engine: the
    scan *and* the partial-aggregate merge stay on the compute substrate —
    the host never combines partials).

    Every input carries a leading shard axis: deltas [S, Nb, Bk], bases /
    counts / block_mask [S, Nb], codes [S, Nb, K, Bk], values
    [S, Nb, V, Bk], with S a multiple of the 1-D ``'scan'`` mesh's size.
    One ``shard_map`` launch places S/msize shard slices on each device;
    a device folds its slices into the block grid of ONE fused-kernel
    launch (zero-count padding blocks are masked off by the visit list),
    and the per-group accumulators tree-reduce across the mesh via
    psum (count, sums) / pmin / pmax — log-depth on a real torus.

    With ``topk = k > 0`` the reduced accumulator is additionally sliced on
    device to its first k non-empty packed groups (packed order ==
    lexicographic key order, so this is a sorted top-k when the query sorts
    by a key-column prefix): returns (ids [k], count [k], sums [V, k],
    mins, maxs, total_rows) and only O(k) lanes cross back to the host.
    Otherwise returns (count [P], sums [V, P], mins, maxs) replicated."""
    from jax.sharding import PartitionSpec as P_

    S, Nb, Bk = deltas.shape
    K, V = codes.shape[2], values.shape[2]
    ndv_t = tuple(int(x) for x in ndv)
    msize = int(mesh.devices.size)
    if S % msize:
        raise ValueError(f"shard count {S} not a multiple of mesh {msize}")

    def body(d, b, c, k, v, m):
        s_loc = d.shape[0]                       # shards on this device
        d2, b2 = d.reshape(s_loc * Nb, Bk), b.reshape(-1)
        c2, m2 = c.reshape(-1), m.reshape(-1)
        k2 = k.reshape(s_loc * Nb, K, Bk)
        v2 = v.reshape(s_loc * Nb, V, Bk)
        if coalesce > 1:                         # caller guarantees tiles
            d2, b2, c2, k2, v2, m2 = coalesce_blocks(  # never span shards
                d2, b2, c2, k2, v2, m2, coalesce)
        cnt, sums, mins, maxs = fused_scan_agg(
            d2, b2, c2, lo, hi, k2, v2, ndv_t, m2, interpret=interpret)
        cnt = jax.lax.psum(cnt, "scan")
        sums = jax.lax.psum(sums, "scan")
        mins = jax.lax.pmin(mins, "scan")
        maxs = jax.lax.pmax(maxs, "scan")
        if not topk:
            return cnt, sums, mins, maxs
        P = cnt.shape[0]
        total = cnt.sum()
        # sorted slice of the accumulator: positions of the first k live
        # groups in packed (== lexicographic key) order
        ids = jnp.argsort(jnp.where(cnt > 0, jnp.arange(P), P))[:topk]
        return (ids.astype(jnp.int32), cnt[ids], sums[:, ids], mins[:, ids],
                maxs[:, ids], total)

    f = jax.shard_map(body, mesh=mesh, in_specs=(P_("scan"),) * 6,
                      out_specs=P_(), check_vma=False)
    return f(deltas, bases, counts, codes, values, block_mask)
