"""Share (%) of executed queries that the device kernel answered
(``ScanStats.used_device``)."""
from bench.stats import share


def read(rec):
    ex = rec.executed()
    return share(sum(q["used_device"] for q in ex), len(ex))
