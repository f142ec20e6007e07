"""Jitted public wrappers for the Pallas kernels.

Dispatch policy:
  * on TPU          → compiled Pallas kernels;
  * on CPU (tests)  → the same kernels in interpret mode (bit-identical
                      semantics, Python-emulated grid);
  * any other backend is an error: there is no silent interpreter there.
  * the other kernels also honour `REPRO_FORCE_REF=1` (the distributed
    dry-run's pure-jnp references from ref.py); the fused scan-agg
    wrappers that serve queries never swap in a reference.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention as _flash
from .hybrid_decode import hybrid_decode as _hybrid_decode
from .ssd_scan import ssd_scan as _ssd
from .columnar_scan import columnar_scan as _columnar_scan
from .dict_groupby import dict_groupby as _dict_groupby
from .fused_scan_agg import coalesce_blocks as _coalesce_blocks
from .fused_scan_agg import fused_scan_agg as _fused_scan_agg
from .fused_scan_agg import sharded_scan_agg as _sharded_scan_agg


def _on_tpu() -> bool:
    """True on TPU, False on CPU (interpret mode); any other backend has no
    kernel path and raises."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"no Pallas TPU kernel path on backend {backend!r}")
    return backend == "tpu"


def _force_ref() -> bool:
    return os.environ.get("REPRO_FORCE_REF", "0") == "1"


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale", "block_q",
                                             "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128):
    if _force_ref():
        return ref.ref_flash(q, k, v, causal=causal, sm_scale=sm_scale,
                             block_k=block_k)
    return _flash(q, k, v, causal=causal, sm_scale=sm_scale, block_q=block_q,
                  block_k=block_k, interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("sm_scale", "skip_eps"))
def hybrid_decode(q, base_k_q, base_v_q, base_k_scale, base_v_scale,
                  base_valid, tail_k, tail_v, tail_len, sketches=None, *,
                  sm_scale: Optional[float] = None, skip_eps: float = 0.0):
    if _force_ref():
        return ref.ref_hybrid_decode(q, base_k_q, base_v_q, base_k_scale,
                                     base_v_scale, base_valid, tail_k, tail_v,
                                     tail_len, sm_scale=sm_scale)
    return _hybrid_decode(q, base_k_q, base_v_q, base_k_scale, base_v_scale,
                          base_valid, tail_k, tail_v, tail_len, sketches,
                          sm_scale=sm_scale, skip_eps=skip_eps,
                          interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, B, C, D_skip=None, *, chunk: int = 64):
    if _force_ref():
        return ref.ref_ssd_chunked(x, dt, A, B, C, chunk=chunk, D_skip=D_skip)
    return _ssd(x, dt, A, B, C, chunk=chunk, D_skip=D_skip,
                interpret=not _on_tpu())


@jax.jit
def columnar_scan(deltas, bases, counts, lo, hi, values=None, block_mask=None):
    if _force_ref():
        return ref.ref_columnar_scan(deltas, bases, counts, lo, hi, values)
    return _columnar_scan(deltas, bases, counts, lo, hi, values, block_mask,
                          interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("ndv", "coalesce"))
def fused_scan_agg(deltas, bases, counts, lo, hi, codes, values,
                   block_mask=None, *, ndv, coalesce=1):
    """``ndv`` is an int (legacy single group key, 2-D codes/values) or a
    per-key tuple (multi-key: codes [Nb, K, Bk], values [Nb, V, Bk]).
    ``coalesce`` > 1 fuses that many adjacent blocks into one kernel tile
    before launch (selectivity-matched tile shapes, see
    ``fused_scan_agg.coalesce_blocks``); the grouped results are identical
    for any factor."""
    if coalesce and int(coalesce) > 1:
        legacy = (codes.ndim == 2 and values.ndim == 2
                  and not isinstance(ndv, (tuple, list)))
        codes3 = codes[:, None, :] if codes.ndim == 2 else codes
        values3 = values[:, None, :] if values.ndim == 2 else values
        ndv_t = ((int(ndv),) if not isinstance(ndv, (tuple, list))
                 else tuple(int(x) for x in ndv))
        mask = (jnp.ones(deltas.shape[0], bool) if block_mask is None
                else block_mask)
        d2, b2, c2, k2, v2, m2 = _coalesce_blocks(
            deltas, bases, counts, codes3, values3, mask, int(coalesce))
        out = _fused_scan_agg(d2, b2, c2, lo, hi, k2, v2, ndv_t, m2,
                              interpret=not _on_tpu())
        if legacy:
            cnt, sums, mins, maxs = out
            return cnt, sums[0], mins[0], maxs[0]
        return out
    return _fused_scan_agg(deltas, bases, counts, lo, hi, codes, values, ndv,
                           block_mask, interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("ndv", "mesh", "coalesce",
                                             "topk"))
def sharded_scan_agg(deltas, bases, counts, lo, hi, codes, values,
                     block_mask=None, *, ndv, mesh, coalesce=1, topk=0):
    """Single-launch sharded device fan-out: inputs carry a leading shard
    axis [S, ...] split over ``mesh``'s 'scan' axis by one ``shard_map``
    launch; each device runs the fused scan-agg kernel over its shard
    slices and the per-group partials tree-reduce ON DEVICE via
    psum/pmin/pmax — no host-side partial merge.  ``topk=k`` additionally
    slices the reduced accumulator to its first k non-empty packed groups
    on device (returns (ids, count, sums, mins, maxs, total_rows))."""
    if block_mask is None:
        block_mask = jnp.ones(deltas.shape[:2], bool)
    return _sharded_scan_agg(deltas, bases, counts, lo, hi, codes, values,
                             ndv, block_mask, mesh, coalesce=int(coalesce),
                             topk=int(topk), interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("ndv", "block_n"))
def dict_groupby(codes, values, *, ndv: int, block_n: int = 1024):
    if _force_ref():
        return ref.ref_dict_groupby(codes, values, ndv)
    return _dict_groupby(codes, values, ndv, block_n=block_n,
                         interpret=not _on_tpu())


def quantize_kv_blocks(k: jax.Array, block: int):
    """Encode KV [B, H, S, D] into int8 columnar blocks + per-block scales
    (the column-encoding step of major compaction in the KV store).
    Returns (codes int8 [B,H,Nb,Bk,D], scales f32 [B,H,Nb,1,1])."""
    B, H, S, D = k.shape
    assert S % block == 0
    nb = S // block
    kb = k.reshape(B, H, nb, block, D).astype(jnp.float32)
    scale = jnp.maximum(jnp.abs(kb).max(axis=(3, 4), keepdims=True), 1e-8) / 127.0
    codes = jnp.clip(jnp.round(kb / scale), -127, 127).astype(jnp.int8)
    return codes, scale
