"""Production mesh construction + per-(arch, shape) sharding rules.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state — the dry-run driver
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any
jax import and only then builds the mesh.

Mesh shapes (TPU v5e, 256 chips/pod):

  single pod:  (16, 16)      axes ('data', 'model')
  multi-pod:   (2, 16, 16)   axes ('pod', 'data', 'model')

The 'pod' axis is a pure data-parallel axis by default (the better roofline
choice for every assigned workload — see EXPERIMENTS.md §Perf); it can also
carry the 2-stage pipeline (train/pipeline.py) or the compressed-gradient
boundary (optim/compress.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType

from repro.models.config import ModelConfig, ShapeConfig
from repro.sharding import MeshRules


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_smoke_mesh(shape: Tuple[int, ...] = (2, 2),
                    axes: Tuple[str, ...] = ("data", "model")
                    ) -> jax.sharding.Mesh:
    """Tiny mesh for CPU multi-device tests (requires host-device override)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_scan_mesh(n_shards: Optional[int] = None) -> jax.sharding.Mesh:
    """1-D ``'scan'`` mesh for the sharded scan fan-out
    (core/partition.py): one axis over the available devices, clamped to
    the logical shard count.  On a real multi-chip host the axis is a real
    multi-device axis and the single-launch collective route
    (``kernels.fused_scan_agg.sharded_scan_agg``) tree-reduces partials
    across it with psum/pmin/pmax; on a single-device host this
    degenerates to a (1,) mesh and the fan-out runs its shards
    sequentially inside one launch."""
    ndev = len(jax.devices())
    size = max(1, min(n_shards or ndev, ndev))
    return jax.make_mesh((size,), ("scan",), axis_types=(AxisType.Auto,))


def scan_launch_shape(n_shards: int,
                      mesh: Optional[jax.sharding.Mesh] = None
                      ) -> Tuple[jax.sharding.Mesh, int]:
    """Mesh + padded logical-shard count for the single-launch collective
    fan-out: the shard count rounds up to a multiple of the 'scan' axis
    size so the [S, ...] staging splits evenly across devices (padding
    shards are zero-count and masked off inside the kernel)."""
    mesh = mesh if mesh is not None else make_scan_mesh(n_shards)
    size = int(mesh.devices.size)
    return mesh, -(-max(n_shards, 1) // size) * size


def scan_shard_devices(n_shards: int,
                       mesh: Optional[jax.sharding.Mesh] = None) -> list:
    """Round-robin assignment of logical scan shards onto the scan mesh's
    devices (shard i -> device i mod mesh size) — the per-shard-launch
    (host-merge) device route."""
    mesh = mesh if mesh is not None else make_scan_mesh(n_shards)
    devs = list(mesh.devices.reshape(-1))
    return [devs[i % len(devs)] for i in range(n_shards)]


def make_rules(cfg: ModelConfig, shape: Optional[ShapeConfig],
               mesh: Optional[jax.sharding.Mesh]) -> MeshRules:
    """The per-cell sharding policy (single source of truth for the dry-run).

    * train/prefill:  batch/fsdp over ('pod','data'); tp over 'model';
                      prefill caches shard their seq dim over 'model'.
    * decode_32k:     KV seq over 'model' (batch covers 'data').
    * long_500k:      batch=1 — KV blocks shard over the *flattened*
                      ('data','model') axis; the hybrid-store decode merges
                      partial (m, l, acc) across it (distributed
                      merge-on-read, DESIGN.md §4).
    """
    rules = MeshRules(mesh=mesh)
    if cfg.n_experts:
        rules = rules.with_moe(cfg.moe_sharding)
    if shape is None:
        return rules
    if shape.kind == "decode":
        # Serving sharding (§Perf iteration D1): weights are TP-only —
        # an fsdp'd weight costs one all-gather per layer PER TOKEN at
        # decode (measured 15.7 GB/step on deepseek long_500k), while
        # TP-sharded bf16 weights fit HBM for every assigned arch.
        rules = dataclasses.replace(rules, fsdp=())
    if shape.kind == "decode" and shape.seq_len > 100_000:
        rules = rules.with_kv_seq(("data", "model"))
    elif shape.kind in ("decode", "prefill"):
        rules = rules.with_kv_seq(("model",))
    return rules
