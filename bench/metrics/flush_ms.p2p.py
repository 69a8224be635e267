"""Mean wall time of a ``tick()`` that ran a sweep flush in the traced
window (ms).  Layer: the serving tiers."""


def read(trace, counters):
    flushes = counters.get("flush_s", [])
    if not flushes:
        return None
    return 1e3 * sum(flushes) / len(flushes)
