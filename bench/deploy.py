"""Set-up: the deployment a configuration file describes, built from the
seed through the program's own load path.

A configuration file holds ``tables`` (each a TPC-H ``lineitem`` at its
``scale_factor``, owned by a ``tenant``), ``block_rows``, the durability
settings (``durable``, ``group_commit``), the server's ``workers`` and its
tenants' quotas.  Table ``k`` is generated from ``table_seed(seed, k)``,
so the reference can regenerate it alone.
"""
from __future__ import annotations

import dataclasses
import tempfile
from typing import Any, Dict, List, Sequence

import numpy as np

from . import tpch


def table_seed(seed: int, k: int) -> List[int]:
    return [seed, 100 + k]


def generate(table: Dict[str, Any], seed: int, k: int
             ) -> Dict[str, np.ndarray]:
    return tpch.lineitem(float(table["scale_factor"]), table_seed(seed, k))


@dataclasses.dataclass
class Deployment:
    db: Any                            # repro.core.session.Database
    handles: Dict[str, Any]            # table name -> TableHandle
    tenants: Dict[str, str]            # tenant -> table name
    keys: Dict[str, Dict[str, np.ndarray]]   # table -> l_orderkey, l_pk
    workdir: Any                       # tempfile.TemporaryDirectory

    def close(self) -> None:
        self.workdir.cleanup()


def build(config: Dict[str, Any], seed: int,
          keep_keys: Sequence[str] = ()) -> Deployment:
    """Generate every table from the seed and direct-load it with
    ``bulk_insert`` into one ``Database``; the write-ahead log lives in a
    temporary directory (under ``TMPDIR``) that ``close`` removes.  The
    tables in ``keep_keys`` keep their ``l_orderkey`` and ``l_pk`` for the
    refresh stream's deletes."""
    from repro.core.session import Database
    workdir = tempfile.TemporaryDirectory(prefix="bench-db-")
    db = Database(durable=workdir.name if config.get("durable") else None,
                  group_commit=int(config.get("group_commit", 1)))
    handles, tenants, keys = {}, {}, {}
    for k, t in enumerate(config["tables"]):
        cols = generate(t, seed, k)
        h = db.create_table(t["name"], tpch.SCHEMA,
                            block_rows=int(config["block_rows"]))
        h.bulk_insert(cols)
        handles[t["name"]] = h
        tenants[t["tenant"]] = t["name"]
        if t["name"] in keep_keys:
            keys[t["name"]] = {"l_orderkey": cols["l_orderkey"].copy(),
                               "l_pk": cols["l_pk"].copy()}
        del cols
    return Deployment(db, handles, tenants, keys, workdir)


def quotas(config: Dict[str, Any]) -> Dict[str, Any]:
    """The server's tenant quotas as the configuration states them."""
    from repro.core.serving import TenantQuota
    out = {}
    for name, q in config.get("quotas", {}).items():
        out[name] = TenantQuota(
            budget_rows=float(q.get("budget_rows", float("inf"))),
            latency_class=q.get("latency_class", "interactive"))
    return out
