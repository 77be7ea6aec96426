"""The span profile: idle time named by the program's spans, on a
synthetic trace with known answers and on the trace recorded on a TPU
v5e; and a traced run of each round shape on the CPU, whose readings
come from the program's spans and its `hash_bytes_total` counter."""
import time

import pytest
from jax.profiler import ProfileData

import harness
import span_profile
import trace_reduce
from conftest import TINY_CONFIG
from repro.core.engine import clear_meta_memo
from test_faults import _cell, MIXES
from test_trace_reduce import RECORDED

# device ops [1000, 1150] and [2000, 2100] ns in the window [500, 2500];
# on the host, bench.resolve [1200, 2400] holds the program's
# engine.contrib_meta [1300, 1900] and engine.plan [1900, 2000], and a
# per-leaf hash.fetch [1400, 1500] that names nothing
SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 150000 }
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1200000 duration_ps: 1200000 }
    events { metadata_id: 3 offset_ps: 1300000 duration_ps: 600000 }
    events { metadata_id: 4 offset_ps: 1900000 duration_ps: 100000 }
    events { metadata_id: 5 offset_ps: 1400000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.resolve" } }
  event_metadata { key: 3 value { id: 3 name: "engine.contrib_meta" } }
  event_metadata { key: 4 value { id: 4 name: "engine.plan" } }
  event_metadata { key: 5 value { id: 5 name: "hash.fetch" } }
}
"""


def test_gap_named_by_program_span_inside_bench_span():
    profile = ProfileData.from_text_proto(SYNTHETIC)
    out = span_profile.idle_by_span(profile)
    # the benchmark's reducer names the same gap by its own span
    assert trace_reduce.reduce_trace(profile)["idle_gaps"][0][0] == \
        "bench.resolve"
    assert out["program_gaps"] == [
        ["engine.contrib_meta", pytest.approx(850e-9)],
        ["unattributed", pytest.approx(500e-9)],
        ["bench.resolve", pytest.approx(400e-9)]]
    assert out["idle_split"] == {
        "engine.contrib_meta": pytest.approx(600e-9),
        "unattributed": pytest.approx(500e-9 + 50e-9 + 100e-9),
        "bench.resolve": pytest.approx(100e-9 + 300e-9),
        "engine.plan": pytest.approx(100e-9)}
    assert out["idle_s"] == pytest.approx(2000e-9 - 250e-9)
    assert sum(out["idle_split"].values()) == pytest.approx(out["idle_s"])


def test_no_device_plane_gives_nothing():
    host_only = SYNTHETIC[SYNTHETIC.index("planes {\n  id: 2"):]
    assert span_profile.idle_by_span(
        ProfileData.from_text_proto(host_only)) is None


def test_recorded_trace_without_program_spans_names_as_before():
    """The recorded window holds only `bench.*` spans: the gaps come
    out with the names and lengths the benchmark's reducer gives, and
    the split sums to the window's idle time."""
    profile = ProfileData.from_file(str(RECORDED))
    before = trace_reduce.reduce_trace(profile)
    out = span_profile.idle_by_span(profile)
    assert [n for n, _ in out["program_gaps"]] == \
        [n for n, _ in before["idle_gaps"]]
    assert [t for _, t in out["program_gaps"]] == pytest.approx(
        [t for _, t in before["idle_gaps"]])
    assert out["idle_s"] == pytest.approx(
        before["window_s"] - before["busy_s"])
    assert sum(out["idle_split"].values()) == pytest.approx(out["idle_s"])
    assert out["idle_split"]["bench.contribute"] >= 0.06


def _hashed_bytes_per_round(cell):
    """A round hashes the arriving contribution twice (its content id,
    the planner's leaf digests; once for the whole process, as the
    planner's digests are memoized by content id) and, with a pinned
    base, the whole base once in each replica's resolve."""
    traffic = cell["traffic"]
    weights = harness.Weights(TINY_CONFIG, traffic, 0)
    replicas = 2 if traffic["replica"] == "sync_pair" else 1
    arriving = weights.covered_params(traffic["leaves"])
    base = weights.params * replicas if traffic["pin_base"] else 0
    return weights.itemsize * (2 * arriving + base)


@pytest.mark.parametrize("mix", MIXES)
def test_traced_run_reads_program_spans_and_counters(mix):
    # the planner's digests are memoized for the process: a mix that
    # draws the contributions of an earlier test would hash them once
    clear_meta_memo()
    cell = _cell(mix)
    rec = span_profile.run(cell, 2 ** 31 + 5, 0.3, check_device=False,
                           log=lambda s: None)
    assert rec["correct"] and rec["rounds"]
    readings = {n: f(rec) for n, f in span_profile.READINGS.items()}
    assert all(v is not None and v > 0 for v in readings.values()), \
        readings
    assert readings["hashed_gb"] * 1e9 == _hashed_bytes_per_round(cell)
    for r in rec["rounds"]:
        assert r["spans"]["top_level"] <= r["round_s"]
        assert {"layer1.contribute", "engine.plan", "engine.execute",
                "engine.wait"} <= set(r["spans"])
        assert ("layer1.gc" in r["spans"]) is \
            cell["traffic"]["retract"]


def test_readings_absent_from_an_untraced_record():
    rec = harness.run_cell(_cell("wa_window"), 3, 0.3, False,
                           t_start=time.perf_counter(),
                           check_device=False, log=lambda s: None)
    assert all(f(rec) is None for f in span_profile.READINGS.values())
