"""Median round time over the window: from the call into
`Replica.contribute` to the merged model resident (host clock)."""
import statistics


def read(run):
    return statistics.median(r["round_s"] for r in run["rounds"])
