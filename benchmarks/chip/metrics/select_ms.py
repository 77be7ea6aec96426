"""Device time of the exact TIES trim's selection per round: the seconds
of `jit__select_quantile` (`strategies/select.py`) in the traced
window's `device_ops`, over the window's rounds, in ms. Absent without
a trace, and where the selection did not run or is not among the
trace's top device programs."""
SELECT_OP = "jit__select_quantile"


def read(run):
    trace = run["trace"]
    if trace is None or not run["rounds"]:
        return None
    seconds = [s for name, s in trace["device_ops"] if name == SELECT_OP]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / len(run["rounds"])
