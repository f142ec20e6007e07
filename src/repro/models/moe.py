"""Mixture-of-Experts with shard_map token dispatch.

The paper's vectorized Data Shuffle + HashGroupBy operators appear here at LM
scale: tokens are *grouped by expert id* (a low-NDV dictionary group-by —
see kernels/dict_groupby.py for the device kernel of the same primitive) and
*shuffled* across the mesh with all_to_all.

Two sharding schemes (cfg.moe_sharding):
  'ep'  — many small experts (kimi-k2: 384): experts sharded over the
          flattened (data, model) axes; dispatch = all_to_all over both.
  'tp'  — few large experts (grok-1: 8): experts sharded over data (padded),
          expert ffn dim sharded over model; dispatch = all_to_all over data,
          down-projection psum over model (Megatron-style expert TP).

Dispatch is sort-based with a static per-(device, expert) capacity — no
[T, E, C] one-hot ever materializes (that tensor is ~20 TB for the assigned
shapes).  Over-capacity tokens are dropped (classic GShard behaviour) and the
drop count is an auxiliary output, surfaced as a training metric.

The same `_local_dispatch/_local_combine` math runs without collectives when
rules.mesh is None (CPU smoke tests), so the distributed path's arithmetic is
unit-tested directly against a dense oracle.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.layers import _init
from repro.sharding import MeshRules


def init_moe(cfg: ModelConfig, key, n_layers: int) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    d, fe, E = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    return {
        "router": _init(ks[0], (n_layers, d, E), scale=0.02),
        "experts": {
            "w1": _init(ks[1], (n_layers, E, d, fe)),
            "w3": _init(ks[2], (n_layers, E, d, fe)),
            "w2": _init(ks[3], (n_layers, E, fe, d)),
        },
    }


def _capacity(n_tokens: int, n_experts: int, top_k: int, cf: float) -> int:
    """Per-expert buffer capacity.

    §Perf iteration K1: the old floor of 4 (lane alignment) made decode
    pay 24× the useful expert FLOPs for kimi-k2 (8 local tokens × top-8
    across 384 experts ⇒ ideal cap 1, padded to 4).  Alignment only pays
    when the buffer is large; tiny buffers keep their exact size."""
    c = int(n_tokens * top_k * cf / n_experts) + 1
    return c if c < 4 else ((c + 3) // 4) * 4


def _local_dispatch(x_flat, logits, top_k: int, n_experts: int, capacity: int):
    """Sort-based dispatch on one shard's tokens.

    x_flat: [T, d]; logits: [T, E].
    Returns (buf [E, C, d], combine metadata) with over-capacity drops.
    """
    T = x_flat.shape[0]
    gates, eids = jax.lax.top_k(logits, top_k)                  # [T, k]
    gates = jax.nn.softmax(gates, axis=-1)
    flat_e = eids.reshape(-1)                                   # [T*k]
    flat_t = jnp.repeat(jnp.arange(T), top_k)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)                    # group by expert
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # slot within the expert group = rank - first rank of that expert
    counts = jnp.bincount(flat_e, length=n_experts)             # [E]
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    slot = jnp.arange(T * top_k) - starts[se]
    keep = slot < capacity
    slot = jnp.where(keep, slot, 0)
    dest = se * capacity + slot                                 # [T*k]
    buf = jnp.zeros((n_experts * capacity, x_flat.shape[1]), x_flat.dtype)
    upd = jnp.where(keep[:, None], x_flat[st], 0)
    buf = buf.at[dest].add(upd)                                 # scatter (unique dests)
    dropped = (~keep).sum()
    meta = (st, sg, dest, keep)
    return buf.reshape(n_experts, capacity, -1), meta, dropped


def _local_combine(y_buf, meta, n_tokens: int):
    """Inverse of dispatch: gather expert outputs back, weighted by gates."""
    st, sg, dest, keep = meta
    d = y_buf.shape[-1]
    flat = y_buf.reshape(-1, d)
    contrib = flat[dest] * (sg * keep)[:, None]
    out = jnp.zeros((n_tokens, d), y_buf.dtype)
    return out.at[st].add(contrib)


def _expert_ffn(buf, w1, w3, w2, psum_axes):
    """buf: [E_loc, C*, d]; weights [E_loc, d, fe]/[E_loc, fe, d]."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w1))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w3)
    y = jnp.einsum("ecf,efd->ecd", h, w2)
    if psum_axes:
        y = jax.lax.psum(y, psum_axes)
    return y


def moe_ffn(cfg: ModelConfig, rules: MeshRules, lp: Dict[str, Any],
            x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (y [B, S, d], dropped_fraction scalar)."""
    B, S, d = x.shape
    E, k, cf = cfg.n_experts, cfg.top_k, cfg.capacity_factor
    router = lp["router"]
    w1, w3, w2 = lp["experts"]["w1"], lp["experts"]["w3"], lp["experts"]["w2"]

    if rules.mesh is None:
        # single-device reference path (same math, no collectives)
        xf = x.reshape(B * S, d)
        logits = (xf @ router.astype(x.dtype)).astype(jnp.float32)
        cap = _capacity(B * S, E, k, cf)
        buf, meta, dropped = _local_dispatch(xf, logits, k, E, cap)
        y = _expert_ffn(buf, w1.astype(x.dtype), w3.astype(x.dtype),
                        w2.astype(x.dtype), ())
        out = _local_combine(y, meta, B * S).reshape(B, S, d)
        return out, dropped / (B * S * k)

    mesh = rules.mesh
    ep_axes = tuple(a for a in rules.ep if a in mesh.axis_names)
    etp_axes = tuple(a for a in rules.etp if a in mesh.axis_names)
    batch_axes = tuple(a for a in rules.batch if a in mesh.axis_names)
    Bsh = rules.axis_size("batch")
    if B % max(Bsh, 1) != 0:   # e.g. long_500k decode (B=1): replicate tokens
        batch_axes = ()
        Bsh = 1
    n_ep = rules.axis_size("ep")
    E_pad = ((E + n_ep - 1) // n_ep) * n_ep
    T_loc = (B // Bsh) * S
    cap = _capacity(T_loc, E_pad, k, cf)

    x_spec = P(batch_axes if batch_axes else None, None, None)
    w_spec = P(ep_axes if ep_axes else None,
               None,
               etp_axes if etp_axes else None)
    w2_spec = P(ep_axes if ep_axes else None,
                etp_axes if etp_axes else None,
                None)

    def local(xl, router_l, w1l, w3l, w2l):
        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        xf = xl.reshape(T, d)
        logits = (xf @ router_l.astype(xl.dtype)).astype(jnp.float32)
        if E_pad > E:
            logits = jnp.pad(logits, ((0, 0), (0, E_pad - E)),
                             constant_values=-1e30)
        buf, meta, dropped = _local_dispatch(xf, logits, k, E_pad, cap)
        # Data Shuffle: all_to_all so each shard receives its experts' tokens
        if ep_axes:
            n = n_ep
            sendbuf = buf.reshape(n, E_pad // n, cap, d)
            recv = jax.lax.all_to_all(sendbuf, ep_axes, split_axis=0,
                                      concat_axis=0, tiled=False)
            recv = recv.reshape(n, E_pad // n, cap, d)
            recv = recv.transpose(1, 0, 2, 3).reshape(E_pad // n, n * cap, d)
        else:
            recv = buf
        y = _expert_ffn(recv, w1l.astype(xl.dtype), w3l.astype(xl.dtype),
                        w2l.astype(xl.dtype), etp_axes)
        if ep_axes:
            n = n_ep
            y = y.reshape(E_pad // n, n, cap, d).transpose(1, 0, 2, 3)
            y = jax.lax.all_to_all(y, ep_axes, split_axis=0, concat_axis=0,
                                   tiled=False)
            y = y.reshape(E_pad, cap, d)
        out = _local_combine(y, meta, T).reshape(Bl, Sl, d)
        return out, (dropped / (T * k)).astype(jnp.float32)[None]

    out, dropped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w2_spec),
        out_specs=(x_spec, P(batch_axes if batch_axes else None)),
        check_vma=False,
    )(x, router, w1 if E_pad == E else jnp.pad(w1, ((0, E_pad - E), (0, 0), (0, 0))),
      w3 if E_pad == E else jnp.pad(w3, ((0, E_pad - E), (0, 0), (0, 0))),
      w2 if E_pad == E else jnp.pad(w2, ((0, E_pad - E), (0, 0), (0, 0))))
    return out, dropped.mean()
