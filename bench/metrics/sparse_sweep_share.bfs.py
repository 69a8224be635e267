"""Share of the traced window's sweeps that the direction choice ran in
the sparse form (%), from the results' ``direction_counts``
(push, pull, sparse).  Layer: the engine's direction choice."""


def read(trace, counters):
    counts = counters.get("direction_counts")
    if not counts or not sum(counts):
        return None
    return 100.0 * counts[2] / sum(counts)
