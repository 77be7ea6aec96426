"""Median per round of the program's `engine.execute` spans (the
executor; it waits for each dispatch group, so device time is inside),
in ms. Absent without the program's tracer."""
import statistics


def read(run):
    if not run["rounds"] or "execute_s" not in run["rounds"][0]:
        return None
    return 1e3 * statistics.median(r["execute_s"] for r in run["rounds"])
