"""Answered queries a second: every query sent in the window over the time
from the window's start to its last answer (closed loop: the streams stop
sending at the close and the last answers drain after it)."""


def read(rec):
    done = [q["done"] for q in rec.answered()]
    if not done:
        return None
    return len(done) / (max(done) - rec.window_start)
