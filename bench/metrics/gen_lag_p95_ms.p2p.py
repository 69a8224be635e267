"""95th percentile of how late each query was submitted after its due
time (ms).  One thread runs the load and the service, so this includes
waiting behind a flush.  Layer: the load generator."""
import numpy as np


def read(trace, counters):
    lag = counters.get("lag_s", [])
    if not lag:
        return None
    return 1e3 * float(np.percentile(lag, 95))
