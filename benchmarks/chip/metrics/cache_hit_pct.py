"""Share of leaf lookups that hit the replica's `EngineCache` over the
window: hits / (hits + misses), in %."""


def read(run):
    hits, misses = run["counters"]["hits"], run["counters"]["misses"]
    if hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
