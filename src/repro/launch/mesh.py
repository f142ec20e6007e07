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
