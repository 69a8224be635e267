"""Mean sweeps per ``h.apsp`` call over the traced window, from the
results' ``sweeps``: the work a call did, beside its speed, so that a
level shift in ``teps`` reads as more work or as slower work.  Layer:
the sweep loop and its forms."""


def read(trace, counters):
    sweeps = counters.get("sweeps", [])
    if not sweeps:
        return None
    return sum(sweeps) / len(sweeps)
