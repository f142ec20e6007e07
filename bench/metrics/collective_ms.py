"""Device time (ms) of the cross-chip collectives per device answer: the
summed seconds of the trace's top device operations (``breakdown``'s
``device_ops``) whose opcode is a collective (all-reduce and its
-start/-done, all-gather, reduce-scatter, collective-permute, all-to-all),
summed over the chips, over the device-answered queries.  None where the
top operations hold no collective or no query was answered on the
device."""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def read(rec):
    t = rec.trace
    if t is None or not rec.launches:
        return None
    secs = [s for name, s in t.device_ops
            if name.partition(":")[0].startswith(COLLECTIVES)]
    if not secs:
        return None
    return sum(secs) / len(rec.launches) * 1e3
