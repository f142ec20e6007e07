#!/usr/bin/env python
"""Compile every launch shape the benchmark's cells can send to the fused
scan-aggregate kernel, for a described TPU v5e (``v5e:2x2``), with no chip:

    JAX_PLATFORMS=cpu python3 bench/compile_check.py

A shape is (shards S, blocks per shard, rows per block, keys, values, group
domain, tile).  For each cell's tables and query classes it compiles the
single-launch collective route on one chip for every shard count the
planner can pick there (1 to ``2 * (cores // workers)``, 13 cores on the
chip's host) with the tile the executor would clamp to.  What the TPU
compiler refuses here (layout, tiling, VMEM) costs no chip time.
"""
from __future__ import annotations

import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CHIP_HOST_CORES = 13
MAX_TILE = 16                  # DEVICE_TILE_ROWS / block_rows at 1,024


def shapes():
    """(cell, class, S, nbp, bk, ndv, V, tile) for every cell of
    BENCHMARK.json."""
    import numpy as np
    from bench import spec, tpch
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    sample = tpch.lineitem(0.01, 0)
    out = set()
    for w in bench["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        bk = int(cell.config["block_rows"])
        workers = int(cell.config.get("workers", 4))
        max_s = 2 * max(1, CHIP_HOST_CORES // workers)
        for t in cell.config["tables"]:
            rows = int(round(float(t["scale_factor"]) * tpch.SF1_ROWS))
            nb = -(-rows // bk)
            for c in cell.queries["classes"]:
                ndv = tuple(int(np.unique(sample[g]).shape[0])
                            for g in c["group_by"])
                v = max(1, len({a[1] for a in c["aggs"] if a[1]}))
                for s in range(1, max_s + 1):
                    nbp = -(-nb // s)
                    tile = min(MAX_TILE, nb)
                    while nbp % tile:
                        tile -= 1
                    out.add((c["name"], s, nbp, bk, ndv, v, tile))
    return sorted(out)


def main() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    jax.config.update("jax_enable_compilation_cache", False)
    fsa = importlib.import_module("repro.kernels.fused_scan_agg")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1), ("scan",))
    shard = NamedSharding(mesh, PartitionSpec("scan"))
    rep = NamedSharding(mesh, PartitionSpec())
    todo = shapes()
    print(f"[compile] {len(todo)} launch shapes", flush=True)
    for name, s, nbp, bk, ndv, v, tile in todo:
        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=shard)
        args = (sds((s, nbp, bk), jnp.int32), sds((s, nbp), jnp.int32),
                sds((s, nbp), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
                sds((s, nbp, len(ndv), bk), jnp.int32),
                sds((s, nbp, v, bk), jnp.float32),
                sds((s, nbp), jnp.bool_))
        f = jax.jit(lambda d, b, c, lo, hi, k, x, m, ndv=ndv, tile=tile:
                    fsa.sharded_scan_agg(d, b, c, lo, hi, k, x, ndv, m, mesh,
                                         coalesce=tile, interpret=False))
        t0 = time.perf_counter()
        compiled = f.lower(*args).compile()
        if "tpu_custom_call" not in compiled.as_text():
            raise SystemExit(f"{name} S={s}: no Mosaic kernel in the program")
        print(f"[compile] ok {name} S={s} nbp={nbp} bk={bk} ndv={ndv} V={v} "
              f"tile={tile} seconds={time.perf_counter() - t0:.2f}",
              flush=True)


if __name__ == "__main__":
    main()
