"""Median per round of the program's `engine.plan` spans (the planner,
with its base-leaf digests when a base is pinned), in ms. Absent
without the program's tracer."""
import statistics


def read(run):
    if not run["rounds"] or "plan_s" not in run["rounds"][0]:
        return None
    return 1e3 * statistics.median(r["plan_s"] for r in run["rounds"])
