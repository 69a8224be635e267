"""Host time per ``h.apsp`` call: the wall time of the benchmark's span
around the call minus the device-busy time inside it, averaged over the
traced calls (ms).  Layer: the facade and engine shell."""


def read(trace, counters):
    spans = trace.named("apsp")
    if not spans:
        return None
    return 1e3 * sum((e - s) - trace.busy_s(s, e) for s, e in spans) \
        / len(spans)
