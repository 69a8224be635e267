"""Share of the traced window's answers that the row cache or the
landmark oracle gave, with no sweep (%).  Layer: the serving tiers."""


def read(trace, counters):
    tiers = counters.get("tiers", {})
    total = sum(tiers.values())
    if not total:
        return None
    return 100.0 * (tiers.get("cache", 0) + tiers.get("oracle", 0)) / total
