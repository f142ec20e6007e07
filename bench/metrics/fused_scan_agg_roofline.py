"""Share (%) of its roofline the fused scan-aggregate kernel reached: the
least time of the work of every device-answered query (``bench/work.py``)
over the kernel's summed device time in the trace."""


def read(rec):
    t = rec.trace
    if t is None or not t.kernel_n or not rec.launches:
        return None
    return 100.0 * sum(w["least_s"] for w in rec.launches) / t.kernel_s
