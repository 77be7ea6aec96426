"""The trace reducer on a synthetic trace with known answers, and on a
trace recorded on a TPU v5e (a jitted matmul and an eager sort under
`bench.*` annotations, three times)."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

import trace_reduce

RECORDED = Path(__file__).resolve().parent / "data" / "tpu_window.xplane.pb"

# device ops [1000, 1100] and [1050, 1150] ns overlap; a third op at
# [3000, 3100] lies outside the window [500, 2500]
SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 50000 duration_ps: 100000 }
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 100000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 150000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "sort.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_ties(123)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1200000 duration_ps: 1200000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.contribute" } }
}
"""


def test_synthetic_trace_reduces_to_known_numbers():
    out = trace_reduce.reduce_trace(ProfileData.from_text_proto(SYNTHETIC))
    assert out["window_s"] == pytest.approx(2000e-9)
    assert out["busy_s"] == pytest.approx(150e-9)
    assert out["device_ops"] == [["jit_ties", pytest.approx(150e-9)]]
    assert out["idle_gaps"] == [
        ["bench.contribute", pytest.approx(1350e-9)],
        ["unattributed", pytest.approx(500e-9)]]


def test_no_device_plane_gives_nothing():
    host_only = SYNTHETIC[SYNTHETIC.index("planes {\n  id: 2"):]
    assert trace_reduce.reduce_trace(
        ProfileData.from_text_proto(host_only)) is None


def _busy_by_sweep(events, lo, hi):
    """Busy time by a sweep over interval edges (a second algorithm)."""
    edges = sorted({lo, hi} | {x for s, e in events for x in (s, e)
                               if lo <= x <= hi})
    busy = 0.0
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        if any(s <= mid <= e for s, e in events):
            busy += b - a
    return busy


def test_recorded_tpu_trace():
    profile = ProfileData.from_file(str(RECORDED))
    out = trace_reduce.reduce_trace(profile)
    window = [(s, e) for name, s, e in trace_reduce.host_spans(profile)
              if name == "bench.window"]
    (w0, w1), = window
    plane = next(p for p in profile.planes if p.name == "/device:TPU:0")
    ops = [(ev.start_ns, ev.start_ns + ev.duration_ns) for line in
           plane.lines if line.name == "XLA Ops" for ev in line.events]
    assert out["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert out["busy_s"] == pytest.approx(
        _busy_by_sweep(ops, w0, w1) * 1e-9)
    assert 0 < out["busy_s"] < out["window_s"]
    names = [n for n, _ in out["device_ops"]]
    assert names[:2] == ["jit__lambda", "jit_sort"]
    # the host slept 20 ms under bench.contribute, three times
    assert [n for n, _ in out["idle_gaps"][:3]] == ["bench.contribute"] * 3
    assert all(t >= 0.02 for _, t in out["idle_gaps"][:3])
