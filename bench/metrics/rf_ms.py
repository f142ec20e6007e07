"""Mean wall time (ms) of the window's refresh functions, first statement to
the return of ``flush_wal``: their summed time over their count."""


def read(rec):
    rf = [r["seconds"] for r in rec.refreshes if r["error"] is None]
    return sum(rf) / len(rf) * 1e3 if rf else None
