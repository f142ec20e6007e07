"""The one traffic generator: queries, arrivals, tenants and refresh writes,
all drawn from ``--seed`` and the data files of a cell.

A traffic file (``<bench>/traffic/<name>.json``) holds:

* ``queries``: the query mix to draw from (``<bench>/queries/<mix>.json``);
* ``loop``: ``"closed"`` or ``"open"``;
* closed loop, ``streams``: how many clients each tenant owns (one each
  in the TPC-H throughput test's shape); a client sends its tenant's next
  query as soon as its last one is answered;
* open loop, ``rate_qps`` arrivals a second, due on a fixed schedule, and
  ``tenants``: popularity weights of the tenants the queries go to;
* ``hints``: keyword arguments of every ``QueryServer.submit``;
* ``writes`` (optional): a paced refresh stream into one tenant's table:
  ``functions`` (``rf1`` inserts ``orders_per_sf * SF`` new orders' lines,
  ``rf2`` deletes every line of as many existing orders) run in turn from
  ``first_s`` every ``period_s`` seconds of the window.

A query mix holds classes, each with a popularity weight (``share``), one
range predicate whose constant is drawn per query, the group-by keys, the
aggregates and the sort.  The shares are whole numbers.  A tenant's drawn
constants of one class never repeat within a run while the range lasts, so
the result cache only answers true repeats.

Every seed gets the same amount of work.  An open loop's class and tenant
counts are exact over the run and its gaps are the same set of exponential
quantiles, in a seeded order.  A closed loop's class counts are exact over
each block of a tenant's queue of as many queries as the shares add up to
(4 for a 1:3 mix), so that every prefix of the queue, however many queries
a window completes, holds the mix to within one query.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import tpch

STREAM_QPS = 50            # a closed-loop queue lasts the window at up to
                           # this many queries a second per stream


@dataclasses.dataclass(frozen=True)
class RefQuery:
    """A query as plain data, for the reference: rows with ``lo <= column
    <= hi`` (either bound may be None), grouped, aggregated and sorted."""

    column: Optional[str]
    lo: Optional[int]
    hi: Optional[int]
    group_by: Tuple[str, ...]
    aggs: Tuple[Tuple[str, Optional[str], str], ...]   # (op, column, alias)
    sort_by: Tuple[str, ...]


@dataclasses.dataclass
class Item:
    """One request of the schedule."""

    index: int
    cls: str
    tenant: str
    table: str
    ref: RefQuery
    at_s: Optional[float]          # due offset in the window (open loop)

    def query(self):
        """The request as the program's ``Query``."""
        from repro.core.engine import QAgg, Query
        from repro.core.relation import Predicate, PredOp
        r = self.ref
        preds = ()
        if r.column is not None:
            if r.lo is not None and r.hi is not None:
                preds = (Predicate(r.column, PredOp.BETWEEN, r.lo, r.hi),)
            elif r.hi is not None:
                preds = (Predicate(r.column, PredOp.LE, r.hi),)
            else:
                preds = (Predicate(r.column, PredOp.GE, r.lo),)
        return Query(preds=preds, group_by=r.group_by,
                     aggs=tuple(QAgg(op, col, alias)
                                for op, col, alias in r.aggs),
                     sort_by=r.sort_by)


@dataclasses.dataclass
class Refresh:
    """One refresh function of the write stream, planned at set-up."""

    kind: str                      # 'rf1' | 'rf2'
    at_s: float
    table: str
    rows: List[Dict[str, Any]]     # rf1: rows to insert
    pks: List[int]                 # rf2: primary keys to delete


def _const(v) -> int:
    return tpch.day(v) if isinstance(v, str) else int(v)


def exact_sequence(weights: Sequence[float], n: int,
                   rng: np.random.Generator) -> np.ndarray:
    """``n`` indices into ``weights`` with counts in proportion (largest
    remainder), in a seeded order."""
    w = np.asarray(weights, np.float64)
    share = w / w.sum() * n
    counts = np.floor(share).astype(np.int64)
    short = n - int(counts.sum())
    if short:
        counts[np.argsort(-(share - counts), kind="stable")[:short]] += 1
    return rng.permutation(np.repeat(np.arange(len(w)), counts))


def exp_gaps(n: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson stream at ``rate``: the same
    set of exponential quantiles for every seed, in a seeded order."""
    q = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-q) / rate)


def _blocks(weights: Sequence[int], n: int,
            rng: np.random.Generator) -> np.ndarray:
    """``n`` indices drawn block by block, each block of ``sum(weights)``
    exact in proportion."""
    b = sum(weights)
    return np.concatenate([exact_sequence(weights, b, rng)
                           for _ in range(-(-n // b))])[:n]


class Schedule:
    """The requests of one run.  An open loop sends them in index order.
    A closed loop holds one queue per tenant, ``queues``: (its request
    indices, in order, and how many streams send them)."""

    def __init__(self, traffic: Dict[str, Any], mix: Dict[str, Any],
                 tables: Dict[str, str], seed: int, seconds: float):
        self.loop = traffic["loop"]
        self.hints = dict(traffic.get("hints", {}))
        self.classes = mix["classes"]
        self.tables = tables
        rng = np.random.default_rng([seed, 1])
        cls_w = [c["share"] for c in self.classes]
        if not all(isinstance(w, int) and w > 0 for w in cls_w):
            raise ValueError(f"class shares must be whole numbers: {cls_w}")
        if self.loop == "open":
            ten_w = traffic["tenants"]
            self.tenant_names = sorted(ten_w)
            n = int(round(float(traffic["rate_qps"]) * seconds))
            gaps = exp_gaps(n, float(traffic["rate_qps"]), rng)
            self.at = np.cumsum(gaps) - gaps[0]
            self.cls_idx = exact_sequence(cls_w, n, rng)
            self.ten_idx = exact_sequence(
                [ten_w[t] for t in self.tenant_names], n, rng)
            self.queues: List[Tuple[range, int]] = []
        elif self.loop == "closed":
            owned = traffic["streams"]
            self.tenant_names = sorted(owned)
            per = sum(cls_w) * -(-int(STREAM_QPS * max(seconds, 1))
                                 // sum(cls_w))
            self.queues, lens = [], []
            for name in self.tenant_names:
                m = int(owned[name])
                self.queues.append((range(sum(lens), sum(lens) + m * per), m))
                lens.append(m * per)
            n = sum(lens)
            self.at = None
            self.cls_idx = np.concatenate([_blocks(cls_w, ln, rng)
                                           for ln in lens])
            self.ten_idx = np.repeat(np.arange(len(lens)), lens)
        else:
            raise ValueError(f"unknown loop {self.loop!r}")
        self.n = n
        # a tenant's j-th query of class k takes the j-th distinct draw of
        # the class's predicate constant
        self.rank = np.zeros(n, np.int64)
        self.offsets: Dict[Tuple[int, int], np.ndarray] = {}
        for k, c in enumerate(self.classes):
            for t in range(len(self.tenant_names)):
                at_tk = np.nonzero((self.ten_idx == t) &
                                   (self.cls_idx == k))[0]
                self.rank[at_tk] = np.arange(at_tk.shape[0])
                p = c.get("pred")
                if p is None:
                    continue
                lo, hi = p["offset"]
                perm = np.random.default_rng([seed, 2, k, t]).permutation(
                    np.arange(int(lo), int(hi) + 1))
                self.offsets[(t, k)] = np.resize(perm, max(1, len(at_tk)))

    def first(self, n: int) -> List[int]:
        """The first ``n`` requests of a run: in order (open loop), or the
        tenants' queues in turn (closed loop)."""
        if self.loop == "open":
            return list(range(min(n, self.n)))
        per = max(len(q) for q, _ in self.queues)
        return [q[j] for j in range(per) for q, _ in self.queues
                if j < len(q)][:n]

    def __len__(self) -> int:
        return self.n

    def item(self, i: int) -> Item:
        k, t = int(self.cls_idx[i]), int(self.ten_idx[i])
        off = None if (t, k) not in self.offsets \
            else int(self.offsets[(t, k)][int(self.rank[i])])
        tenant = self.tenant_names[t]
        return self.make(i, k, off, tenant,
                         None if self.at is None else float(self.at[i]))

    def probe(self, k: int, fraction: float, tenant: str) -> Item:
        """A request of class ``k`` whose predicate constant lies at
        ``fraction`` of the class's range (warm-up)."""
        p = self.classes[k].get("pred")
        off = None
        if p is not None:
            a, b = p["offset"]
            off = int(round(a + (b - a) * fraction))
        return self.make(-1, k, off, tenant, None)

    def make(self, i: int, k: int, off: Optional[int], tenant: str,
             at_s: Optional[float]) -> Item:
        c = self.classes[k]
        p = c.get("pred")
        col = lo = hi = None
        if p is not None:
            col = p["column"]
            v = _const(p["from"]) + off
            if p["op"] == "le":
                hi = v
            elif p["op"] == "ge":
                lo = v
            elif p["op"] == "between":
                lo, hi = v, v + int(p["width"])
            else:
                raise ValueError(f"unknown predicate op {p['op']!r}")
        ref = RefQuery(col, lo, hi, tuple(c["group_by"]),
                       tuple((a[0], a[1], a[2]) for a in c["aggs"]),
                       tuple(c.get("sort_by", ())))
        return Item(i, c["name"], tenant, self.tables[tenant], ref, at_s)


def plan_refreshes(traffic: Dict[str, Any], table: Dict[str, Any],
                   base: Dict[str, np.ndarray], seed: int,
                   seconds: float) -> List[Refresh]:
    """The refresh functions due in the window, with their rows: RF1 adds
    ``orders_per_sf * SF`` new orders after the last one (TPC-H §2.5.2),
    RF2 deletes all lines of as many existing orders (§2.5.3), no order
    twice.  ``base`` holds the table's ``l_orderkey`` and ``l_pk``."""
    w = traffic.get("writes")
    if not w:
        return []
    sf = float(table["scale_factor"])
    n_orders = max(1, int(round(sf * float(w["orders_per_sf"]))))
    base_orders = max(1, int(round(sf * tpch.ORDERS_PER_SF)))
    times = np.arange(float(w["first_s"]), seconds, float(w["period_s"]))
    kinds = [w["functions"][k % len(w["functions"])]
             for k in range(len(times))]
    n_rf2 = kinds.count("rf2")
    victims = np.random.default_rng([seed, 3]).choice(
        base_orders, n_orders * n_rf2, replace=False)
    names = tpch.SCHEMA.names
    out, n1, n2 = [], 0, 0
    for at, kind in zip(times, kinds):
        if kind == "rf1":
            cols = tpch.lineitem(n_orders / tpch.ORDERS_PER_SF,
                                 [seed, 4, n1],
                                 first_order=base_orders + n1 * n_orders)
            rows = [{c: cols[c][i].item() for c in names}
                    for i in range(len(cols["l_pk"]))]
            out.append(Refresh(kind, float(at), table["name"], rows, []))
            n1 += 1
        elif kind == "rf2":
            keys = tpch.order_key(victims[n2 * n_orders:(n2 + 1) * n_orders])
            pks = base["l_pk"][np.isin(base["l_orderkey"], keys)]
            out.append(Refresh(kind, float(at), table["name"], [],
                               [int(p) for p in pks]))
            n2 += 1
        else:
            raise ValueError(f"unknown refresh function {kind!r}")
    return out

