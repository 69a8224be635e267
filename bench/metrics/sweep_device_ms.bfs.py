"""Device-busy time inside the traced ``h.apsp`` spans divided by the
sweeps their results report (ms per sweep).  Layer: the sweep loop and
its forms."""


def read(trace, counters):
    spans = trace.named("apsp")
    sweeps = sum(counters.get("sweeps", []))
    if not spans or not sweeps:
        return None
    return 1e3 * sum(trace.busy_s(s, e) for s, e in spans) / sweeps
