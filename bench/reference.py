"""The plain reference and the comparison that decides ``correct``.

The reference imports nothing of the program.  It regenerates each table
from the seed with the benchmark's own generator, applies the refresh
writes the run acknowledged up to a query's snapshot, and answers the query
with a straightforward filter, group and float64 sum in numpy.

What the comparison holds the program to, each number with its limit:

* ``max_rel_err``: the largest relative error of any float aggregate of any
  answer.  The device sums float32 planes in one float32 accumulator per
  group over thousands of grid steps; on a TPU v5e sound runs read
  up to about 4e-6 at SF 1, a one-pass bfloat16 contraction in the kernel
  about 9e-5, and the reference computed from bfloat16 inputs (the
  control) 3e-4 to 2e-3.  ``PERF.md`` gives the readings the limit was
  set from.
* ``wrong_answers``: answers whose group keys or counts differ from the
  reference's (exact).
* ``unanswered``: queries due in the window that raised or never resolved.
* ``lost_writes``: acknowledged refresh writes not read back after the
  window (an inserted row not returned as written, a deleted one
  returned).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .traffic import RefQuery

LIMITS = {"max_rel_err": 2e-5, "wrong_answers": 0, "unanswered": 0,
          "lost_writes": 0}


def _key(v) -> Any:
    """A group key as a plain comparable value."""
    if isinstance(v, (bytes, np.bytes_)):
        return bytes(v)
    if isinstance(v, (np.integer, int)):
        return int(v)
    if isinstance(v, (np.floating, float)):
        return float(v)
    return v


class RefTable:
    """One table's rows as the reference sees them: the generated baseline
    plus the acknowledged refresh writes, each with the snapshot timestamp
    that made it visible."""

    def __init__(self, columns: Dict[str, np.ndarray], pk: str = "l_pk"):
        self.cols = columns
        self.pk = pk
        self.inserts: List[Tuple[int, Dict[str, Any]]] = []
        self.deletes: List[Tuple[int, int]] = []
        self._code_cache: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}

    def _codes(self, col: str) -> Tuple[np.ndarray, np.ndarray]:
        """Sorted distinct values of a baseline column and each row's index
        into them, computed once."""
        hit = self._code_cache.get(col)
        if hit is None:
            u, c = np.unique(self.cols[col], return_inverse=True)
            hit = self._code_cache[col] = (u, c.reshape(-1))
        return hit

    def _state(self, ts: Optional[int], values: Sequence[str],
               keys: Sequence[str]):
        """Value columns and group-key codes as of snapshot ``ts`` (None:
        after every write): the baseline less the deleted rows, plus the
        inserted ones."""
        dels = [pk for t, pk in self.deletes if ts is None or t <= ts]
        gone = set(dels)
        ins = [r for t, r in self.inserts
               if (ts is None or t <= ts) and r[self.pk] not in gone]
        keep = (~np.isin(self.cols[self.pk], np.asarray(dels))
                if dels else None)

        def part(a: np.ndarray) -> np.ndarray:
            return a if keep is None else a[keep]

        out = {}
        for c in values:
            base = part(self.cols[c])
            if ins:
                base = np.concatenate(
                    [base, np.asarray([r[c] for r in ins], base.dtype)])
            out[c] = base
        codes = []
        for g in keys:
            u, c = self._codes(g)
            c = part(c)
            if ins:
                add = np.asarray([r[g] for r in ins], u.dtype)
                at = np.minimum(np.searchsorted(u, add), len(u) - 1)
                if (u[at] != add).any():       # a value the baseline lacks
                    u = np.unique(np.concatenate([u, add]))
                    c = np.searchsorted(u, part(self.cols[g]))
                    at = np.searchsorted(u, add)
                c = np.concatenate([c, at])
            codes.append((u, c))
        return out, codes

    def prepare(self, q: RefQuery, ts: Optional[int]):
        """What ``q`` reads at snapshot ``ts``: its value columns, the
        predicate's row mask, each row's packed group code (over the
        product of the keys' value sets) with that product, and the keys'
        value sets."""
        vals = sorted({c for _, c, _ in q.aggs if c is not None})
        names = sorted(set(vals) | ({q.column} if q.column else set()))
        st, codes = self._state(ts, names, q.group_by)
        n = len(codes[0][1]) if codes else len(st[names[0]])
        mask = np.ones(n, bool)
        if q.column is not None:
            col = st[q.column]
            if q.lo is not None:
                mask &= col >= q.lo
            if q.hi is not None:
                mask &= col <= q.hi
        packed = np.zeros(n, np.int64)
        size = 1
        for u, c in codes:
            packed = packed * len(u) + c
            size *= len(u)
        return {c: st[c] for c in vals}, mask, packed, size, \
            [u for u, _ in codes]

    def answer(self, q: RefQuery, ts: Optional[int] = None
               ) -> List[Dict[str, Any]]:
        """Rows of ``q`` at snapshot ``ts``, summed in float64."""
        vals, mask, packed, size, uniq = self.prepare(q, ts)
        p = packed[mask]
        cnt = np.bincount(p, minlength=size)
        sums = {c: np.bincount(p, weights=v[mask].astype(np.float64),
                               minlength=size) for c, v in vals.items()}
        return emit(q, uniq, cnt, sums)


def emit(q: RefQuery, uniq: Sequence[np.ndarray], cnt: np.ndarray,
         sums: Dict[str, np.ndarray]) -> List[Dict[str, Any]]:
    """Result rows from per-group counts and sums: every non-empty group,
    its keys decoded, sorted by the query's sort keys."""
    rows = []
    for g in np.nonzero(cnt)[0]:
        r: Dict[str, Any] = {}
        rem = int(g)
        for name, u in reversed(list(zip(q.group_by, uniq))):
            r[name] = _key(u[rem % len(u)])
            rem //= len(u)
        for op, c, alias in q.aggs:
            if op == "count":
                r[alias] = int(cnt[g])
            elif op == "sum":
                r[alias] = float(sums[c][g])
            elif op == "avg":
                r[alias] = float(sums[c][g]) / int(cnt[g])
            else:
                raise ValueError(f"reference has no aggregate {op!r}")
        rows.append(r)
    rows.sort(key=lambda r: tuple(r[s] for s in q.sort_by))
    return rows


def compare(q: RefQuery, got: Sequence[Dict[str, Any]],
            want: Sequence[Dict[str, Any]]) -> Tuple[Optional[str], float]:
    """(what differs exactly, or None; the largest relative float error)."""
    gk = [tuple(_key(r[k]) for k in q.group_by) for r in got]
    wk = [tuple(r[k] for k in q.group_by) for r in want]
    if gk != wk:
        return f"groups {gk} != reference {wk}", 0.0
    worst = 0.0
    for g, w, key in zip(got, want, wk):
        for op, _, alias in q.aggs:
            a, b = g[alias], w[alias]
            if op == "count":
                if int(a) != b:
                    return f"{alias} {a} != {b} for {key}", worst
                continue
            if a is None:
                return f"{alias} missing for {key}", worst
            err = abs(float(a) - b) / max(abs(b), 1e-30)
            worst = max(worst, err)
    return None, worst


def verdict(numbers: Dict[str, float]) -> bool:
    """Every compared number within its limit."""
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)
