"""The executor's high-water mark of stacked contribution bytes over
the window (`EngineCache.peak_stacked` of the replica's cache), in GB."""


def read(run):
    peak = run["counters"]["peak_stacked_bytes"]
    return peak / 1e9 if peak > 0 else None
