"""Share of the traced window in which no operation ran on the device
(%), in the batch cells.  Layer: the device."""


def read(trace, counters):
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)
