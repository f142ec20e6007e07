"""Median time (ms) of ``pushdown.stage_device``, the host loop that builds
the kernel's input planes, from the harness's span around it."""
from bench.stats import median


def read(rec):
    v = median(rec.spans.get("bench.stage_device", []))
    return None if v is None else v * 1e3
