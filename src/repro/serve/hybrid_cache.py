"""Hybrid KV store: the paper's LSM column store (C1) on TPU decode.

Mapping (DESIGN.md §2):

  baseline data  (columnar SSTables)   → compacted KV *blocks*: int8 codes +
                                         one scale per (head, block) — the
                                         column-encoded baseline (S1), read
                                         without decompression (dequant is
                                         fused into the score matmul);
  incremental    (row MemTable)        → the *tail*: most recent < Bk tokens
                                         in native dtype, appended row-wise;
  merge-on-read                        → decode attention = online-softmax
                                         over tail + surviving blocks,
                                         LSE-merged;
  minor compaction                     → ``compact``: full tail → one new
                                         encoded block + zone-map sketch;
  data-skipping index (S2)             → per-block max-key-L2-norm sketches;
                                         a *budgeted top-K* visit list prunes
                                         blocks whose score upper bound
                                         can't matter.  RoPE preserves key
                                         norms, so sketches survive rotation.

Distribution (long_500k, DESIGN.md §4): blocks shard over the flattened
``kv_seq`` mesh axes.  Each shard prunes *its* blocks, computes partial
(m, l, acc), and the shards LSE-merge with psum — distributed merge-on-read,
the same combiner as the local two-source merge.  No KV bytes ever cross
the interconnect; only (m, l, acc) triples (G·hd + 2 floats per head).

Tail capacity == block size, so a full tail compacts into exactly one block
(the MemTable freeze → minor SSTable step).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.sharding import MeshRules

BLOCK = 128          # tokens per compacted block (MXU-aligned)
NEG = -1e30


@dataclasses.dataclass(frozen=True)
class HybridSpec:
    """Static geometry of a hybrid cache."""
    n_layers: int
    batch: int
    n_kv_heads: int
    head_dim: int
    max_blocks: int          # Nb — capacity in compacted blocks
    budget: int              # max blocks *visited* per (b, head) (S2 prune)
    block: int = BLOCK

    @property
    def max_len(self) -> int:
        return self.max_blocks * self.block + self.block


def hybrid_spec(cfg: ModelConfig, batch: int, max_len: int,
                budget_frac: float = 0.25) -> HybridSpec:
    nb = max(1, max_len // BLOCK)
    budget = max(1, min(nb, int(nb * budget_frac)))
    return HybridSpec(cfg.n_layers, batch, cfg.n_kv_heads, cfg.hd, nb, budget)


def init_hybrid_cache(spec: HybridSpec, dtype=jnp.bfloat16) -> Dict[str, Any]:
    L, B, H, D = spec.n_layers, spec.batch, spec.n_kv_heads, spec.head_dim
    Nb, Bk = spec.max_blocks, spec.block
    return {
        "pos": jnp.zeros((B,), jnp.int32),
        "tail_len": jnp.zeros((B,), jnp.int32),
        "n_blocks": jnp.zeros((B,), jnp.int32),
        "kq": jnp.zeros((L, B, H, Nb, Bk, D), jnp.int8),
        "vq": jnp.zeros((L, B, H, Nb, Bk, D), jnp.int8),
        "kscale": jnp.zeros((L, B, H, Nb), jnp.float32),
        "vscale": jnp.zeros((L, B, H, Nb), jnp.float32),
        "sketch": jnp.zeros((L, B, H, Nb), jnp.float32),
        "tail_k": jnp.zeros((L, B, H, Bk, D), dtype),
        "tail_v": jnp.zeros((L, B, H, Bk, D), dtype),
    }


# ---------------------------------------------------------------------------
# Per-layer ops (called inside the decode layer scan; no leading L dim)
# ---------------------------------------------------------------------------


def append_tail(layer_cache: Dict[str, jax.Array], k: jax.Array, v: jax.Array,
                tail_len: jax.Array) -> Dict[str, jax.Array]:
    """Row-format append (the MemTable write).  k, v: [B, H, 1, D]."""
    tk, tv = layer_cache["tail_k"], layer_cache["tail_v"]
    Bk = tk.shape[2]
    onehot = jax.nn.one_hot(tail_len, Bk, dtype=tk.dtype)      # [B, Bk]
    sel = onehot[:, None, :, None]
    out = dict(layer_cache)
    out["tail_k"] = tk * (1 - sel) + sel * k.astype(tk.dtype)
    out["tail_v"] = tv * (1 - sel) + sel * v.astype(tv.dtype)
    return out


def _quantize_block(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: [..., Bk, D] → (int8 codes, scale [...])."""
    amax = jnp.maximum(jnp.abs(x.astype(jnp.float32)).max(axis=(-2, -1)), 1e-8)
    scale = amax / 127.0
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None, None]),
                     -127, 127).astype(jnp.int8)
    return codes, scale


def compact(cache: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Minor compaction: full tails become one encoded block + sketch.

    Whole-cache (all layers at once), jit-safe, batch-elementwise: batch
    entries whose tail is full (tail_len == Bk) compact; others unchanged.
    Cost: one select over the block arrays — amortized O(bytes/Bk) per
    decode step, the TPU analogue of the paper's background compaction.
    """
    Bk = cache["tail_k"].shape[3]
    full = cache["tail_len"] == Bk                              # [B]
    nb = cache["n_blocks"]                                      # [B]
    Nb = cache["kq"].shape[3]

    kq_new, ks_new = _quantize_block(cache["tail_k"])           # [L,B,H,Bk,D]
    vq_new, vs_new = _quantize_block(cache["tail_v"])
    sk_new = jnp.linalg.norm(
        cache["tail_k"].astype(jnp.float32), axis=-1).max(axis=-1)  # [L,B,H]

    onehot = (jnp.arange(Nb)[None, :] == nb[:, None]) & full[:, None]  # [B,Nb]
    sel6 = onehot[None, :, None, :, None, None]
    sel4 = onehot[None, :, None, :]

    out = dict(cache)
    out["kq"] = jnp.where(sel6, kq_new[:, :, :, None], cache["kq"])
    out["vq"] = jnp.where(sel6, vq_new[:, :, :, None], cache["vq"])
    out["kscale"] = jnp.where(sel4, ks_new[:, :, :, None], cache["kscale"])
    out["vscale"] = jnp.where(sel4, vs_new[:, :, :, None], cache["vscale"])
    out["sketch"] = jnp.where(sel4, sk_new[:, :, :, None], cache["sketch"])
    out["n_blocks"] = jnp.where(full, nb + 1, nb)
    out["tail_len"] = jnp.where(full, 0, cache["tail_len"])
    # tails are overwritten in place by subsequent appends; no need to zero
    return out


# ---------------------------------------------------------------------------
# Merge-on-read decode attention (zone-map pruned, distributed)
# ---------------------------------------------------------------------------


def _local_partials(qg, kq, vq, ksc, vsc, sketch, n_blocks_local,
                    budget: int, sm_scale: float):
    """Partial online-softmax over this shard's surviving blocks.

    qg [B,H,G,D]; kq/vq [B,H,Nb,Bk,D] int8; ksc/vsc/sketch [B,H,Nb];
    n_blocks_local [B] — valid blocks in THIS shard.
    Returns (m, l, acc): [B,H,G], [B,H,G], [B,H,G,D] float32.
    """
    B, H, G, D = qg.shape
    Nb, Bk = kq.shape[2], kq.shape[3]
    K = min(budget, Nb)
    qf = qg.astype(jnp.float32) * sm_scale

    valid = jnp.arange(Nb)[None, None, :] < n_blocks_local[:, None, None]
    qnorm = jnp.linalg.norm(qf, axis=-1).max(axis=2)            # [B,H]
    bounds = jnp.where(valid, qnorm[..., None] * sketch, NEG)   # [B,H,Nb]
    _, bids = jax.lax.top_k(bounds, K)                          # [B,H,K]
    bvalid = jnp.take_along_axis(valid, bids, axis=2)           # [B,H,K]

    def take(x):
        return jnp.take_along_axis(
            x, bids[:, :, :, None, None], axis=2)               # [B,H,K,Bk,D]

    kb = take(kq).astype(jnp.float32) * \
        jnp.take_along_axis(ksc, bids, 2)[..., None, None]
    vb = take(vq).astype(jnp.float32) * \
        jnp.take_along_axis(vsc, bids, 2)[..., None, None]
    s = jnp.einsum("bhgd,bhkcd->bhgkc", qf, kb)                 # [B,H,G,K,Bk]
    ok = bvalid[:, :, None, :, None]
    s = jnp.where(ok, s, NEG)
    m = s.max(axis=(3, 4))                                      # [B,H,G]
    p = jnp.where(ok, jnp.exp(s - m[..., None, None]), 0.0)
    l = p.sum(axis=(3, 4))
    acc = jnp.einsum("bhgkc,bhkcd->bhgd", p, vb)
    return m, l, acc


def _tail_partials(qg, tail_k, tail_v, tail_len, sm_scale: float):
    """Partials over the row-format tail.  tail_k/v [B,H,Bk,D]."""
    qf = qg.astype(jnp.float32) * sm_scale
    Bk = tail_k.shape[2]
    s = jnp.einsum("bhgd,bhcd->bhgc", qf, tail_k.astype(jnp.float32))
    ok = (jnp.arange(Bk)[None, :] < tail_len[:, None])[:, None, None, :]
    s = jnp.where(ok, s, NEG)
    m = s.max(axis=-1)
    p = jnp.where(ok, jnp.exp(s - m[..., None]), 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhgc,bhcd->bhgd", p, tail_v.astype(jnp.float32))
    return m, l, acc


def _lse_combine(parts):
    """Merge [(m,l,acc), ...] — the LSM merge-on-read combiner."""
    m = functools.reduce(jnp.maximum, [p[0] for p in parts])
    l = sum(jnp.exp(p[0] - m) * p[1] for p in parts)
    acc = sum(jnp.exp(p[0] - m)[..., None] * p[2] for p in parts)
    return m, l, acc


def hybrid_attention(cfg: ModelConfig, rules: MeshRules,
                     layer_cache: Dict[str, jax.Array], q: jax.Array,
                     budget: int) -> jax.Array:
    """Merge-on-read decode over one layer's hybrid cache.

    q: [B, Hq, D] (already roped).  Returns [B, Hq, D] attention output.
    Tail is merged by shard 0 only; blocks merge via psum LSE (see module
    docstring).  With budget >= Nb and exact scales this equals dense
    attention over the full history (tests/test_hybrid_cache.py).
    """
    B, Hq, D = q.shape
    H = cfg.n_kv_heads
    G = Hq // H
    sm = D ** -0.5
    qg = q.reshape(B, H, G, D)
    kv_axes = tuple(a for a in rules.kv_seq
                    if rules.mesh is not None and a in rules.mesh.axis_names)

    if not kv_axes:
        bp = _local_partials(qg, layer_cache["kq"], layer_cache["vq"],
                             layer_cache["kscale"], layer_cache["vscale"],
                             layer_cache["sketch"], layer_cache["n_blocks"],
                             budget, sm)
        tp = _tail_partials(qg, layer_cache["tail_k"], layer_cache["tail_v"],
                            layer_cache["tail_len"], sm)
        m, l, acc = _lse_combine([bp, tp])
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(B, Hq, D).astype(q.dtype)

    mesh = rules.mesh
    nsh = rules.axis_size("kv_seq")
    local_budget = max(1, budget // nsh)
    Nb = layer_cache["kq"].shape[2]
    assert Nb % nsh == 0, (Nb, nsh)

    blk_spec = P(None, None, kv_axes, None, None)
    sc_spec = P(None, None, kv_axes)

    def local(qg, kq, vq, ksc, vsc, sk, n_blocks, tk, tv, tl):
        idx = jax.lax.axis_index(kv_axes)
        nb_loc = Nb // nsh
        # blocks are filled in order: shard i owns [i·nb_loc, (i+1)·nb_loc)
        n_local = jnp.clip(n_blocks - idx * nb_loc, 0, nb_loc)
        bp = _local_partials(qg, kq, vq, ksc, vsc, sk, n_local,
                             local_budget, sm)
        tp = _tail_partials(qg, tk, tv, tl, sm)
        first = (idx == 0)
        tp = (jnp.where(first, tp[0], NEG), jnp.where(first, tp[1], 0.0),
              jnp.where(first, tp[2][..., :], 0.0) * first)
        m, l, acc = _lse_combine([bp, tp])
        gm = jax.lax.pmax(m, kv_axes)
        w = jnp.exp(m - gm)
        gl = jax.lax.psum(l * w, kv_axes)
        gacc = jax.lax.psum(acc * w[..., None], kv_axes)
        return gacc / jnp.maximum(gl, 1e-30)[..., None]

    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), blk_spec, blk_spec, sc_spec, sc_spec, sc_spec, P(),
                  P(), P(), P()),
        out_specs=P(),
        check_vma=False,
    )(qg, layer_cache["kq"], layer_cache["vq"], layer_cache["kscale"],
      layer_cache["vscale"], layer_cache["sketch"], layer_cache["n_blocks"],
      layer_cache["tail_k"], layer_cache["tail_v"], layer_cache["tail_len"])
    return out.reshape(B, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Construction from a dense history (tests / prefill hand-off)
# ---------------------------------------------------------------------------


def from_dense(spec: HybridSpec, k: jax.Array, v: jax.Array,
               lengths: jax.Array, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Build a hybrid cache from dense per-layer KV [L, B, H, S, D].

    Full blocks are compacted (encoded + sketched); the remainder lands in
    the tail — exactly the state after a prefill + all minor compactions.
    """
    L, B, H, S, D = k.shape
    Bk, Nb = spec.block, spec.max_blocks
    cache = init_hybrid_cache(spec, dtype)
    nfull = S // Bk
    assert nfull <= Nb
    kb = k[:, :, :, :nfull * Bk].reshape(L, B, H, nfull, Bk, D)
    vb = v[:, :, :, :nfull * Bk].reshape(L, B, H, nfull, Bk, D)
    n_blocks = jnp.minimum(lengths // Bk, nfull)
    kq, ks = _quantize_block(kb)
    vq, vs = _quantize_block(vb)
    sk = jnp.linalg.norm(kb.astype(jnp.float32), axis=-1).max(axis=-1)
    pad = Nb - nfull
    pad6 = ((0, 0),) * 3 + ((0, pad),) + ((0, 0),) * 2
    pad4 = ((0, 0),) * 3 + ((0, pad),)
    cache["kq"] = jnp.pad(kq, pad6)
    cache["vq"] = jnp.pad(vq, pad6)
    cache["kscale"] = jnp.pad(ks, pad4)
    cache["vscale"] = jnp.pad(vs, pad4)
    cache["sketch"] = jnp.pad(sk, pad4)
    cache["n_blocks"] = n_blocks.astype(jnp.int32)
    tail_len = lengths - n_blocks * Bk
    # remainder tokens → tail (gather relative to each sequence's block end)
    tpos = n_blocks[None, :, None, None] * Bk + jnp.arange(Bk)[None, None, None]
    tpos = jnp.broadcast_to(tpos, (L, B, H, Bk))
    tidx = jnp.minimum(tpos, S - 1)
    cache["tail_k"] = jnp.take_along_axis(
        k, tidx[..., None], axis=3).astype(dtype)
    cache["tail_v"] = jnp.take_along_axis(
        v, tidx[..., None], axis=3).astype(dtype)
    cache["tail_len"] = tail_len.astype(jnp.int32)
    cache["pos"] = lengths.astype(jnp.int32)
    return cache


def cache_pspecs(spec: HybridSpec, rules: MeshRules):
    """PartitionSpec pytree: blocks shard over kv_seq, batch over batch."""
    kv = tuple(a for a in rules.kv_seq
               if rules.mesh is not None and a in rules.mesh.axis_names)
    kv = kv if kv else None
    b = None  # B==1 for long-context; keep replicated unless batch divides
    return {
        "pos": P(), "tail_len": P(), "n_blocks": P(),
        "kq": P(None, b, None, kv, None, None),
        "vq": P(None, b, None, kv, None, None),
        "kscale": P(None, b, None, kv),
        "vscale": P(None, b, None, kv),
        "sketch": P(None, b, None, kv),
        "tail_k": P(), "tail_v": P(),
    }
