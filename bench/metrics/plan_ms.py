"""Median planning time (ms) per admitted ticket: the ``ob.plan`` spans
(plan-cache lookup, ``Database.compile`` on a miss) of each request,
summed."""
from bench.programspans import named, per_request_ms
from bench.stats import median


def read(rec):
    return median(per_request_ms(named(rec, "ob.plan")))
