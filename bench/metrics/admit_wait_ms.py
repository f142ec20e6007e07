"""Median wait (ms) in the server's admission queue: ``Ticket.dispatched_at``
minus ``Ticket.submitted`` over the executed queries."""
from bench.stats import median


def read(rec):
    v = median([q["dispatched"] - q["submitted"] for q in rec.executed()
                if q["dispatched"] is not None])
    return None if v is None else v * 1e3
