"""Share (%) of answered queries served from the result cache or coalesced
onto an identical query in flight."""
from bench.stats import share


def read(rec):
    ans = rec.answered()
    return share(sum(q["cache_hit"] or q["coalesced"] for q in ans), len(ans))
