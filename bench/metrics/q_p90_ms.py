"""90th-percentile latency (ms) of every request due in the window."""
from bench.stats import percentile


def read(rec):
    v = percentile([q["latency_s"] for q in rec.answered()], 90)
    return None if v is None else v * 1e3
