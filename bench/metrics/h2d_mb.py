"""Median host-to-device megabytes (1e6 bytes) per device-answered query:
``ScanStats.h2d_bytes``, the host arguments handed to the device over
every launch, chunk and retry of the query."""
from bench.stats import median


def read(rec):
    v = median([q["h2d_bytes"] for q in rec.executed()
                if q["used_device"] and q.get("h2d_bytes") is not None])
    return None if v is None else v / 1e6
