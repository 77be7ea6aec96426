"""Median per round of the benchmark's own spans around
`Replica.contribute`, `Replica.retract` and the tombstone collection,
those of them that the mix's rounds make: the API facade and Layer 1 on
the host (hashing, OR-Set add/remove, store), in ms."""
import statistics


def read(run):
    return 1e3 * statistics.median(
        r["contribute_s"] + r.get("retract_s", 0.0) + r.get("gc_s", 0.0)
        for r in run["rounds"])
