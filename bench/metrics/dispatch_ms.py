"""Median time (ms) of a kernel dispatch, the ``ob.dispatch`` span: the
launch's host arguments handed to the device, then the enqueue."""
from bench.programspans import named, wall_ms
from bench.stats import median


def read(rec):
    return median([wall_ms(s) for s in named(rec, "ob.dispatch")])
