"""Median wait (ms) in the server's queue before the scheduler picks the
ticket: ``Ticket.picked_at`` minus ``Ticket.submitted`` over the executed
queries."""
from bench.stats import median


def read(rec):
    v = median([q["picked"] - q["submitted"] for q in rec.executed()
                if q.get("picked") is not None])
    return None if v is None else v * 1e3
