"""Where a table's scan runs: the 1-D ``'scan'`` device mesh, the
baseline's block→chip placement, and the shards of one device answer.

A ``VirtualSSTable``'s encoded baseline blocks are **range-partitioned**
into contiguous shards — boundaries are chosen from the ``SkippingIndex``
leaf sketches (per-block row counts), so shards carry near-equal row weight
and, because baseline blocks are pk-ordered, each shard is a pk range.

On a scan mesh of D > 1 devices the table is a partitioned deployment, as
the Mercury paper's tables are spread over OBServers: its blocks are split
by range into D chip partitions once per baseline generation
(``chip_partitions``, recorded as the ``ob.place`` span), and every device
answer cuts each chip's partition into the same number of shards
(``place_shards``).  An answer's shards are then a whole multiple of D,
shard i runs on chip ⌊i·D/S⌋ on either device route, and block b runs on
the same chip for every query of a generation, whatever fan-out the planner
picked.  A chip with nothing to scan runs zero-count padding.  On one
device the shards are the planner's ``range_partition``.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

from . import spans


@dataclasses.dataclass(frozen=True)
class BlockShard:
    """One shard's contiguous block range [lo_block, hi_block) of the
    baseline (== one pk range, since baseline blocks are pk-ordered)."""

    shard_id: int
    lo_block: int
    hi_block: int
    n_rows: int

    @property
    def n_blocks(self) -> int:
        return self.hi_block - self.lo_block

    def block_ids(self) -> range:
        return range(self.lo_block, self.hi_block)


def _cut(cum: np.ndarray, lo: int, hi: int, n: int
         ) -> List[Tuple[int, int, int]]:
    """``n`` contiguous (lo, hi, rows) ranges of blocks [lo, hi) of near-equal
    row weight, from the cumulative block row counts ``cum``."""
    a, b = int(cum[lo]), int(cum[hi])
    cuts = [int(np.searchsorted(cum, a + (b - a) * s / n, side="left"))
            for s in range(1, n)]
    edges = np.maximum.accumulate(np.asarray([lo] + cuts + [hi]))
    return [(int(edges[s]), int(edges[s + 1]),
             int(cum[edges[s + 1]] - cum[edges[s]])) for s in range(n)]


def _cumulative_rows(base) -> np.ndarray:
    weights = base.cols[base.schema.pk].index.leaf_counts()
    return np.concatenate([[0], np.cumsum(weights)])


def range_partition(base, n_shards: int) -> List[BlockShard]:
    """Split the baseline's blocks into ``n_shards`` contiguous ranges of
    near-equal row weight, read off the skipping-index leaf sketches (no
    data access).  Shards may be empty when there are fewer blocks than
    shards."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if base.n_blocks == 0:
        return [BlockShard(s, 0, 0, 0) for s in range(n_shards)]
    return [BlockShard(s, lo, hi, rows) for s, (lo, hi, rows) in enumerate(
        _cut(_cumulative_rows(base), 0, base.n_blocks, n_shards))]


_PLACE_LOCK = threading.Lock()


def chip_partitions(base, n_chips: int) -> List[BlockShard]:
    """The baseline's ``n_chips`` chip partitions, ``range_partition(base,
    n_chips)``, computed once per baseline generation: a compaction or a
    bulk load swaps the ``VirtualSSTable``, and this cache with it."""
    with _PLACE_LOCK:
        cache = getattr(base, "_chip_partitions", None)
        if cache is None:
            cache = base._chip_partitions = {}
        if n_chips not in cache:
            with spans.span("ob.place"):
                cache[n_chips] = range_partition(base, n_chips)
        return cache[n_chips]


def place_shards(base, n_shards: int, n_chips: int) -> List[BlockShard]:
    """The shards of one device answer on D = ``n_chips`` scan devices:
    each chip partition cut by row weight into k = ⌈n_shards / D⌉ shards
    (at least one), numbered chip by chip, so chip c holds shards
    [c·k, (c+1)·k)."""
    per_chip = max(1, -(-n_shards // n_chips))
    cum = _cumulative_rows(base)
    out: List[BlockShard] = []
    for part in chip_partitions(base, n_chips):
        for lo, hi, rows in _cut(cum, part.lo_block, part.hi_block,
                                 per_chip):
            out.append(BlockShard(len(out), lo, hi, rows))
    return out


def chip_blocks(shards: Sequence[BlockShard], block_mask: np.ndarray,
                n_chips: int) -> int:
    """The most unpruned blocks any one chip scans among ``shards``, shard
    i on chip ⌊i·D/S⌋."""
    per = [0] * n_chips
    for i, s in enumerate(shards):
        per[i * n_chips // len(shards)] += int(
            block_mask[s.lo_block:s.hi_block].sum())
    return max(per)


# ---------------------------------------------------------------------------
# The 'scan' mesh
# ---------------------------------------------------------------------------


def make_scan_mesh(n_shards: Optional[int] = None):
    """1-D ``'scan'`` mesh over the devices JAX sees, clamped to
    ``n_shards`` (the executor passes the baseline's block count).  On a
    multi-chip host the single-launch collective route
    (``kernels.fused_scan_agg.sharded_scan_agg``) tree-reduces partials
    across it with psum/pmin/pmax; on a single-device host it is a (1,)
    mesh and the shards run in one launch."""
    import jax
    from jax.sharding import AxisType
    ndev = len(jax.devices())
    size = max(1, min(n_shards or ndev, ndev))
    return jax.make_mesh((size,), ("scan",), axis_types=(AxisType.Auto,))


def scan_launch_shape(n_shards: int, mesh=None) -> Tuple[object, int]:
    """Mesh + padded logical-shard count for the single-launch collective
    fan-out: the shard count rounds up to a multiple of the 'scan' axis
    size so the [S, ...] staging splits evenly across devices (padding
    shards are zero-count and masked off inside the kernel)."""
    mesh = mesh if mesh is not None else make_scan_mesh(n_shards)
    size = int(mesh.devices.size)
    return mesh, -(-max(n_shards, 1) // size) * size


def scan_shard_devices(n_shards: int, mesh=None) -> list:
    """The device of each logical scan shard on the per-shard-launch
    (host-merge) route: shard i on device ⌊i·D/n_shards⌋, where the
    collective's ``shard_map`` puts it when D divides ``n_shards``."""
    mesh = mesh if mesh is not None else make_scan_mesh(n_shards)
    devs = list(mesh.devices.reshape(-1))
    return [devs[i * len(devs) // n_shards] for i in range(n_shards)]
