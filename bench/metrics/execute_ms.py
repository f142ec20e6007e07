"""Median executor time (ms), ``ScanStats.latency_s`` as ``Database.execute``
stamps it, over the executed queries."""
from bench.stats import median


def read(rec):
    v = median([q["exec_s"] for q in rec.executed()])
    return None if v is None else v * 1e3
