"""TPC-H ``lineitem`` at a given scale factor, generated in bulk from a seed.

The benchmark's own copy of the generator, so that no change to the
program can move the data the benchmark measures on.  Queries are data
(``bench/queries/*.json``), not code here.

Columns and value distributions follow the TPC-H specification v3.0.1,
§4.2.3 (``lineitem`` and the ``orders`` fields it derives from):

* orders: ``SF * 1,500,000`` of them, keys sparse in ``[1, SF * 6,000,000]``
  (only the first 8 of every 32 key values are used); each has 1–7 lines;
* ``l_partkey`` uniform in ``[1, SF * 200,000]``; ``l_suppkey`` is one of the
  part's four suppliers (the ``partsupp`` formula); ``l_quantity`` uniform
  in ``[1, 50]``; ``l_extendedprice = l_quantity * p_retailprice``;
  ``l_discount`` in ``[0.00, 0.10]``; ``l_tax`` in ``[0.00, 0.08]``;
* ``o_orderdate`` uniform in ``[1992-01-01, 1998-12-31 - 151 days]``;
  ``l_shipdate = o_orderdate + [1, 121]``, ``l_commitdate = o_orderdate +
  [30, 90]``, ``l_receiptdate = l_shipdate + [1, 30]``;
* ``l_returnflag`` is R or A when ``l_receiptdate <= 1995-06-17`` and N
  otherwise; ``l_linestatus`` is O when ``l_shipdate > 1995-06-17`` and F
  otherwise; ``l_shipinstruct`` and ``l_shipmode`` uniform over the spec's
  lists; ``l_comment`` 10–43 characters of the spec's text grammar words.

Types: dates are INT day numbers (days since 1970-01-01), decimals are
FLOAT, the four flag/mode columns and the comment are STR, and the primary
key ``l_pk`` packs ``(l_orderkey, l_linenumber)`` into one INT.

Two liberties, both in what the spec leaves to ``dbgen``'s own random
streams: the line count per order is adjusted so SF 1 holds exactly
6,001,215 rows (``dbgen``'s count), and comments are drawn from a pool of
65,536 grammar sentences instead of one fresh sentence per row.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from repro.core.relation import ColType, schema

SF1_ROWS = 6_001_215
ORDERS_PER_SF = 1_500_000

START_DATE = "1992-01-01"
CURRENT_DATE = "1995-06-17"
END_DATE = "1998-12-31"

INSTRUCTIONS = (b"DELIVER IN PERSON", b"COLLECT COD", b"NONE",
                b"TAKE BACK RETURN")
MODES = (b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB")
# a sample of the §4.2.2.10 grammar's word lists
_WORDS = (
    "foxes ideas theodolites pinto beans instructions dependencies excuses "
    "platelets asymptotes courts dolphins multipliers sauternes warthogs "
    "frets dinos attainments somas Tiresias patterns forges braids hockey "
    "players frays warhorses dugouts notornis epitaphs pearls tithes waters "
    "orbits gifts sheaves depths sentiments decoys realms pains grouches "
    "escapades sleep wake are cajole haggle nag use boost affix detect "
    "integrate maintain nod was lose sublate solve thrash promise engage "
    "hinder print x-ray breach eat grow impress mold poach serve run dazzle "
    "snooze doze unwind kindle play hang believe doubt furious sly careful "
    "blithe quick fluffy slow quiet ruthless thin close dogged daring brave "
    "stealthy permanent enticing idle busy regular final ironic even bold "
    "silent sometimes always never furiously slyly carefully blithely "
    "quickly fluffily about above according to across after against along "
    "alongside of among around at atop before behind beneath beside besides "
    "between beyond by despite during except for from in place of inside "
    "instead of into near of on outside over past since through throughout "
    "to toward under until up upon without with within").split()

SCHEMA = schema(
    ("l_pk", ColType.INT),
    ("l_orderkey", ColType.INT),
    ("l_partkey", ColType.INT),
    ("l_suppkey", ColType.INT),
    ("l_linenumber", ColType.INT),
    ("l_quantity", ColType.FLOAT),
    ("l_extendedprice", ColType.FLOAT),
    ("l_discount", ColType.FLOAT),
    ("l_tax", ColType.FLOAT),
    ("l_returnflag", ColType.STR),
    ("l_linestatus", ColType.STR),
    ("l_shipdate", ColType.INT),
    ("l_commitdate", ColType.INT),
    ("l_receiptdate", ColType.INT),
    ("l_shipinstruct", ColType.STR),
    ("l_shipmode", ColType.STR),
    ("l_comment", ColType.STR),
)


def day(date: str) -> int:
    """Day number (days since 1970-01-01) of an ISO date."""
    return int(np.datetime64(date, "D").astype(np.int64))


def order_key(i: np.ndarray) -> np.ndarray:
    """The sparse ``o_orderkey`` of order ordinal ``i`` (0-based): the first
    8 of every 32 key values."""
    return (i // 8) * 32 + (i % 8) + 1


def pack_pk(orderkey: np.ndarray, linenumber: np.ndarray) -> np.ndarray:
    return orderkey * 8 + linenumber


def _comment_pool(rng: np.random.Generator, n: int = 1 << 16) -> np.ndarray:
    words = np.asarray(_WORDS)
    picks = rng.integers(0, len(words), (n, 8))
    lens = rng.integers(10, 44, n)
    out = [" ".join(words[p])[:k].rstrip() or "ideas"
           for p, k in zip(picks, lens)]
    return np.asarray(out, dtype=np.bytes_)


def lines_per_order(sf: float, rng: np.random.Generator) -> np.ndarray:
    """Lines of each of ``sf * 1.5M`` orders (1–7 each), adjusted by single
    lines so that they add up to ``sf * SF1_ROWS``."""
    n_orders = max(1, int(round(sf * ORDERS_PER_SF)))
    target = max(n_orders, int(round(sf * SF1_ROWS)))
    per = rng.integers(1, 8, n_orders)
    diff = target - int(per.sum())
    if diff:                            # move single orders by one line each
        room = np.nonzero(per < 7 if diff > 0 else per > 1)[0]
        pick = rng.choice(room, abs(diff), replace=False)
        per[pick] += 1 if diff > 0 else -1
    return per


def lineitem(sf: float, seed,
             first_order: int = 0) -> Dict[str, np.ndarray]:
    """Columns of ``lineitem`` for ``sf * 1.5M`` orders starting at order
    ordinal ``first_order``, ``sf * SF1_ROWS`` rows."""
    rng = np.random.default_rng(seed)
    per = lines_per_order(sf, rng)
    n_orders = per.shape[0]
    n = int(per.sum())
    order = np.repeat(np.arange(n_orders, dtype=np.int64) + first_order, per)
    starts = np.cumsum(per) - per
    linenumber = np.arange(n, dtype=np.int64) - np.repeat(starts, per) + 1
    orderkey = order_key(order)

    n_parts = max(1, int(round(sf * 200_000)))
    n_supp = max(4, int(round(sf * 10_000)))
    partkey = rng.integers(1, n_parts + 1, n)
    supp_i = rng.integers(0, 4, n)
    suppkey = (partkey + supp_i * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1
    retail = (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100
    quantity = rng.integers(1, 51, n).astype(np.float64)
    extended = np.round(quantity * retail, 2)
    discount = rng.integers(0, 11, n) / 100.0
    tax = rng.integers(0, 9, n) / 100.0

    orderdate = np.repeat(rng.integers(day(START_DATE),
                                       day(END_DATE) - 151 + 1, n_orders), per)
    shipdate = orderdate + rng.integers(1, 122, n)
    commitdate = orderdate + rng.integers(30, 91, n)
    receiptdate = shipdate + rng.integers(1, 31, n)
    current = day(CURRENT_DATE)
    returnflag = np.where(receiptdate <= current,
                          np.where(rng.integers(0, 2, n) == 0, b"R", b"A"),
                          b"N")
    linestatus = np.where(shipdate > current, b"O", b"F")
    instruct = np.asarray(INSTRUCTIONS)[rng.integers(0, 4, n)]
    mode = np.asarray(MODES)[rng.integers(0, 7, n)]
    comment = _comment_pool(rng)[rng.integers(0, 1 << 16, n)]
    return {
        "l_pk": pack_pk(orderkey, linenumber),
        "l_orderkey": orderkey, "l_partkey": partkey, "l_suppkey": suppkey,
        "l_linenumber": linenumber, "l_quantity": quantity,
        "l_extendedprice": extended, "l_discount": discount, "l_tax": tax,
        "l_returnflag": returnflag, "l_linestatus": linestatus,
        "l_shipdate": shipdate, "l_commitdate": commitdate,
        "l_receiptdate": receiptdate, "l_shipinstruct": instruct,
        "l_shipmode": mode, "l_comment": comment,
    }
