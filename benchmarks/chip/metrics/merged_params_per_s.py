"""Parameters of merged models made per second of the whole window:
rounds completed times the model's parameters over the window's time,
generation of the arriving contributions included (host clock)."""


def read(run):
    return len(run["rounds"]) * run["params"] / run["window_s"]
