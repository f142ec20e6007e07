"""Median time (ms) of ``emit_device_groups``, the ``ob.emit`` span: the
kernel's per-group partials unpacked into result rows and sorted."""
from bench.programspans import named, wall_ms
from bench.stats import median


def read(rec):
    return median([wall_ms(s) for s in named(rec, "ob.emit")])
