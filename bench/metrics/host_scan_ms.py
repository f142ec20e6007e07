"""Median host-path scan time (ms) per query that scanned on the host:
its ``ob.host_scan`` spans (encoded filter, materialization and the
aggregate merge, once the device is not used), summed."""
from bench.programspans import named, per_request_ms
from bench.stats import median


def read(rec):
    return median(per_request_ms(named(rec, "ob.host_scan")))
