"""Peak device memory in use, read after the window and before the
correctness check: `memory_stats()["peak_bytes_in_use"]`, in GB."""


def read(run):
    return run["memory_peak_bytes"] / 1e9
