"""Device time (ms) of the fused scan-aggregate kernel per launch: its
trace events' summed durations over their count."""


def read(rec):
    t = rec.trace
    if t is None or not t.kernel_n:
        return None
    return t.kernel_s / t.kernel_n * 1e3
