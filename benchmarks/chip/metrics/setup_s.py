"""Seconds from process start to the first measured round: JAX start,
generation, the initial contributes and the warm-up round (host
clock)."""


def read(run):
    return run["setup_s"]
