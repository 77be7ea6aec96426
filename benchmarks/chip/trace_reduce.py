"""Reduce a `jax.profiler` trace of one traced window to numbers.

    busy_s      union of the intervals in which an operation ran on a
                device, inside the window, averaged over the devices
    window_s    length of the window: the host annotation that the
                harness puts around the measured rounds
    device_ops  the device programs that took the most time (seconds
                summed over their runs, averaged over the devices; a
                program's name without its fingerprint, `jit_add(123)`
                counts as `jit_add`)
    idle_gaps   the longest idle gaps of the first device inside the
                window, each named by the innermost benchmark span
                (`bench.*` host annotation) that covered its middle

The trace is read with `jax.profiler.ProfileData` (the `.xplane.pb`
file that `jax.profiler.stop_trace` writes); the profiler puts device
and host events on one clock.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
FINGERPRINT = re.compile(r"\(\d+\)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
TOP = 10

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged, non-overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals: List[Interval], lo: float, hi: float
          ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def _line(plane, name: str):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def host_spans(profile) -> List[Tuple[str, float, float]]:
    return [ev for plane in profile.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in _events(line)
            if ev[0].startswith(SPAN_PREFIX)]


def _covering_span(spans, t: float) -> str:
    inside = [(e - s, name) for name, s, e in spans
              if s <= t <= e and name != WINDOW_SPAN]
    return min(inside)[1] if inside else "unattributed"


def reduce_trace(profile) -> Optional[Dict]:
    """The numbers above, or None when the trace holds no device plane
    with operations or no window annotation."""
    spans = host_spans(profile)
    windows = [(e - s, s, e) for name, s, e in spans if name == WINDOW_SPAN]
    devices = [p for p in profile.planes if DEVICE_PLANE.match(p.name)
               and _line(p, OPS_LINE) is not None]
    if not windows or not devices:
        return None
    _, w0, w1 = max(windows)
    busy: List[float] = []
    per_op: Dict[str, float] = {}
    first_gaps: List[Interval] = []
    for n, plane in enumerate(devices):
        ops = union(_clip([(s, e) for _, s, e in
                           _events(_line(plane, OPS_LINE))], w0, w1))
        busy.append(sum(e - s for s, e in ops))
        named = _line(plane, MODULES_LINE) or _line(plane, OPS_LINE)
        for name, s, e in _events(named):
            name = FINGERPRINT.sub("", name)
            for cs, ce in _clip([(s, e)], w0, w1):
                per_op[name] = per_op.get(name, 0.0) + (ce - cs)
        if n == 0:
            edges = [w0] + [x for iv in ops for x in iv] + [w1]
            first_gaps = [(edges[i], edges[i + 1])
                          for i in range(0, len(edges), 2)
                          if edges[i + 1] > edges[i]]
    n_dev = len(devices)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(first_gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": sum(busy) / n_dev * 1e-9,
        "window_s": (w1 - w0) * 1e-9,
        "device_ops": [[name, t / n_dev * 1e-9] for name, t in top_ops],
        "idle_gaps": [[_covering_span(spans, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in top_gaps],
    }
