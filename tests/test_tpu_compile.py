"""Compile-only checks of the scan kernels for a described TPU v5e.

Nothing runs: the TPU compiler builds the kernels for a ``v5e:2x2``
topology that is described, not attached, so a layout, tiling or VMEM
refusal that interpret mode cannot see fails here at no chip time.  The
topology and everything built from it live in module-scoped fixtures, so
importing this file never loads the TPU library.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import cost, pushdown
from repro.core.skipping import DEFAULT_BLOCK_ROWS

# ``repro.kernels`` re-exports the jitted wrapper under the module's name
fsa = importlib.import_module("repro.kernels.fused_scan_agg")

SF1_ROWS = 6_001_215
SF1_BLOCKS = -(-SF1_ROWS // DEFAULT_BLOCK_ROWS)
SF1_TILES = -(-SF1_ROWS // cost.DEVICE_TILE_ROWS)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices).reshape(4), ("scan",))


def _shapes(sharding, lead, bk, k, v):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(lead + shape, dt, sharding=sharding)
    return (sds((bk,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32),
            sds((k, bk), jnp.int32), sds((v, bk), jnp.float32),
            sds((), jnp.bool_))


@pytest.mark.parametrize("nb,bk,ndv,v", [
    (SF1_BLOCKS, DEFAULT_BLOCK_ROWS, (3, 2), 4),     # Q1 keys, block height
    (SF1_TILES, cost.DEVICE_TILE_ROWS, (3, 2), 4),   # largest coalesced tile
    (SF1_TILES, cost.DEVICE_TILE_ROWS, (7,), 3),     # ship-mode window
    (16, DEFAULT_BLOCK_ROWS, (pushdown._DEVICE_MAX_GROUPS,), 4),  # group cap
])
def test_fused_scan_agg_compiles_for_v5e(one_chip, nb, bk, ndv, v):
    d, b, c, k, vals, m = _shapes(one_chip, (nb,), bk, len(ndv), v)
    f = jax.jit(lambda d, b, c, lo, hi, k, vals, m: fsa.fused_scan_agg(
        d, b, c, lo, hi, k, vals, ndv, m, interpret=False))
    lo = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = f.lower(d, b, c, lo, lo, k, vals, m).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("topk", [0, 2])
def test_sharded_scan_agg_compiles_on_four_chips(mesh4, topk):
    """The single-launch collective: one kernel per chip, partials reduced
    across the 'scan' axis on device."""
    spec = NamedSharding(mesh4, PartitionSpec("scan"))
    nbp = -(-SF1_BLOCKS // 4)
    d, b, c, k, vals, m = _shapes(spec, (4, nbp), DEFAULT_BLOCK_ROWS, 2, 3)
    f = jax.jit(lambda d, b, c, k, vals, m: fsa.sharded_scan_agg(
        d, b, c, 0, 100, k, vals, (3, 2), m, mesh4,
        coalesce=cost.DEVICE_TILE_ROWS // DEFAULT_BLOCK_ROWS, topk=topk,
        interpret=False))
    text = f.lower(d, b, c, k, vals, m).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
