"""Median time (ms) of one logged write statement, the ``ob.write`` span
over ``LSMStore.insert`` / ``delete``: call to return, the wait for the
store lock and any group-commit WAL flush included."""
from bench.programspans import named, wall_ms
from bench.stats import median


def read(rec):
    return median([wall_ms(s) for s in named(rec, "ob.write")])
