"""Median CPU time (ms) the staging thread itself spent in
``pushdown.stage_device`` (the ``ob.stage`` span's ``cpu_ns``), against
its wall time in ``stage_ms``."""
from bench.programspans import named
from bench.stats import median


def read(rec):
    v = median([s["cpu_ns"] for s in named(rec, "ob.stage")])
    return None if v is None else v * 1e-6
