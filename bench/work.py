"""What the fused scan-aggregate kernel has to do for one query, computed
from the query and the table alone (never from the kernel's own arrays),
and the chip's peaks that bound how fast it can do it.

For a query with one range predicate, ``K`` group keys and ``V`` value
columns over the rows of the blocks that the zone maps cannot prune:

* bytes = rows x (1 predicate + K keys + max(V, 1) values) x 4 B, each
  plane read once as 32-bit words;
* ops = rows x 2 x G x (V + 1), the one-hot contraction of every row
  against the G groups (G = the product of the keys' cardinalities) for
  the V sums and the count.

The least time is the larger of bytes over peak bandwidth and ops over
peak FLOP/s; a kernel's roofline share is that over its device time.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

WORD = 4


def peaks(bench_dir: str, kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind`` from ``peaks.json``; a device the table
    does not hold is an error, never a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(has {sorted(table)})")
    return table[kind]


def kernel_work(rows: int, n_keys: int, n_values: int,
                groups: int) -> Tuple[int, int]:
    """(bytes, ops) of one fused scan-aggregate over ``rows`` rows."""
    planes = 1 + n_keys + max(n_values, 1)
    return rows * planes * WORD, rows * 2 * groups * (n_values + 1)


def zones(column: np.ndarray, block_rows: int):
    """Per block of ``block_rows`` consecutive rows (primary-key order):
    the column's min, max and the block's row count."""
    n = column.shape[0]
    nb = -(-n // block_rows)
    pad = nb * block_rows - n
    mins = np.concatenate([column, np.full(pad, column.max())]) \
        .reshape(nb, block_rows).min(axis=1)
    maxs = np.concatenate([column, np.full(pad, column.min())]) \
        .reshape(nb, block_rows).max(axis=1)
    sizes = np.full(nb, block_rows)
    sizes[-1] = n - (nb - 1) * block_rows
    return mins, maxs, sizes


def unpruned_rows(zone, lo: Optional[int], hi: Optional[int]) -> int:
    """Rows of the blocks whose [min, max] meets ``[lo, hi]``."""
    mins, maxs, sizes = zone
    live = np.ones(mins.shape[0], bool)
    if lo is not None:
        live &= maxs >= lo
    if hi is not None:
        live &= mins <= hi
    return int(sizes[live].sum())


def least_time(bytes_: float, ops: float,
               peak: Dict[str, float]) -> Tuple[float, str]:
    """(seconds, which bound) of the roofline for that work."""
    t_mem = bytes_ / peak["hbm_bytes_per_s"]
    t_ops = ops / peak["bf16_flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "flops")


def cardinality(values: np.ndarray) -> int:
    return int(np.unique(values).shape[0])


def groups_of(cards: Sequence[int]) -> int:
    g = 1
    for c in cards:
        g *= int(c)
    return g
