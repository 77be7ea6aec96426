"""Where a round's time goes, by the program's own spans and counters.

    python3 benchmarks/chip/span_profile.py --workload <cell> \\
        --seeds 11,12 --seconds 51

In one process, on the chip: for each seed, one traced run of the cell
through `harness.run_cell` (set-up, window, check, as the benchmark
runs it), with the program's tracer given `jax.profiler.TraceAnnotation`
as its `annotate` hook, so that the program's spans (`layer1.*`,
`engine.*`, `sync.*`) lie on the profiler's clock beside the device's
operations and the benchmark's `bench.*` spans. Each run prints:

  spans:  per span name, the median over the window's rounds of its
          milliseconds in a round, and of the round's top-level spans;
  obs:    the median change a round makes to each series of the
          replicas' `Replica.metrics()` (summed over the replicas);
  idle:   the window's idle seconds on the first device, split by the
          innermost span (benchmark or program) over each idle instant;
  gaps:   the longest idle gaps, each named by the innermost span over
          its middle;

then one JSON line: the readings of `READINGS` (medians per round, as
the benchmark's per-layer metrics are), the benchmark's own per-layer
readings and `merge_s_p50` of the same traced run, and `correct`.

The benchmark's runs never run this. `harness.py` and `trace_reduce.py`
are as the benchmark has them; for the length of a run this module
wraps four of the calls they make: the tracer the harness builds (to
give it the hook), the replicas' `reset_stats` before the window and
their Layer-1 read after each round (to take the counters there), and
the reduction of the trace (to name the idle time by program spans).
"""
import argparse
import bisect
import contextlib
import json
import statistics
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple
from unittest import mock

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402
import trace_reduce  # noqa: E402

sys.path.insert(0, str(harness.ROOT / "src"))

# host annotations that can name an idle gap: the benchmark's steps and
# the program's spans; the per-leaf `hash.*` spans are too fine for that
NAMING_PREFIXES = ("bench.", "layer1.", "engine.", "sync.")
HASH_SPANS = ("layer1.digest", "engine.contrib_meta", "engine.base_digest")


# ------------------------------------------------------------- the trace


def naming_spans(profile) -> List[Tuple[str, float, float]]:
    return [ev for plane in profile.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in trace_reduce._events(line)
            if ev[0].startswith(NAMING_PREFIXES)]


def _idle(profile, w0: float, w1: float
          ) -> Optional[List[Tuple[float, float]]]:
    """Idle intervals of the first device with operations, inside the
    window (as `trace_reduce.reduce_trace` finds them)."""
    for plane in profile.planes:
        ops = trace_reduce._line(plane, trace_reduce.OPS_LINE)
        if trace_reduce.DEVICE_PLANE.match(plane.name) and ops is not None:
            busy = trace_reduce.union(trace_reduce._clip(
                [(s, e) for _, s, e in trace_reduce._events(ops)], w0, w1))
            edges = [w0] + [x for iv in busy for x in iv] + [w1]
            return [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    return None


def _innermost(spans, w0: float, w1: float
               ) -> List[Tuple[float, float, str]]:
    """The window cut at every span edge, each piece named by the
    innermost span over it (`unattributed` where none is)."""
    edges = sorted({w0, w1} | {x for _, s, e in spans for x in (s, e)
                               if w0 < x < w1})
    return [(a, b, trace_reduce._covering_span(spans, (a + b) / 2))
            for a, b in zip(edges, edges[1:])]


def idle_by_span(profile) -> Optional[Dict]:
    """`idle_gaps` named by benchmark and program spans, and the idle
    seconds of the window split by the innermost span over each idle
    instant; None where `trace_reduce.reduce_trace` finds nothing."""
    spans = naming_spans(profile)
    windows = [(e - s, s, e) for name, s, e in spans
               if name == trace_reduce.WINDOW_SPAN]
    if not windows:
        return None
    _, w0, w1 = max(windows)
    gaps = _idle(profile, w0, w1)
    if gaps is None:
        return None
    pieces = _innermost(spans, w0, w1)
    starts = [a for a, _, _ in pieces]
    split: Dict[str, float] = {}
    for g0, g1 in gaps:
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(pieces) and pieces[i][0] < g1:
            a, b, name = pieces[i]
            overlap = min(b, g1) - max(a, g0)
            if overlap > 0:
                split[name] = split.get(name, 0.0) + overlap * 1e-9
            i += 1
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:trace_reduce.TOP]
    return {
        "idle_s": sum(e - s for s, e in gaps) * 1e-9,
        "idle_split": dict(sorted(split.items(), key=lambda kv: -kv[1])),
        "program_gaps": [[trace_reduce._covering_span(spans, (s + e) / 2),
                          (e - s) * 1e-9] for s, e in longest],
    }


# ------------------------------------------------------------ the rounds


def spans_by_round(spans) -> List[Dict[str, float]]:
    """Seconds summed per span name in each round, and the round's
    top-level program spans under `top_level`. A round starts where a
    top-level `layer1.contribute` starts (each round's first step)."""
    starts = sorted(s.t0 for s in spans
                    if s.name == "layer1.contribute" and s.parent_id is None)
    rounds: List[Dict[str, float]] = [{} for _ in starts]
    for s in spans:
        r = bisect.bisect_right(starts, s.t0) - 1
        if r < 0:
            continue
        rounds[r][s.name] = rounds[r].get(s.name, 0.0) + s.duration
        if s.parent_id is None:
            rounds[r]["top_level"] = rounds[r].get("top_level", 0.0) \
                + s.duration
    return rounds


def _summed(snapshots: List[Dict[str, float]]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for snap in snapshots:
        for k, v in snap.items():
            out[k] = out.get(k, 0.0) + v
    return out


def _changes(before: Dict[str, float], after: Dict[str, float]
             ) -> Dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


def _median_ms(run, *names) -> Optional[float]:
    if not run["rounds"] or "spans" not in run["rounds"][0]:
        return None
    return 1e3 * statistics.median(
        sum(r["spans"].get(n, 0.0) for n in names) for r in run["rounds"])


def _hashed_gb(run) -> Optional[float]:
    if not run["rounds"] or "obs" not in run["rounds"][0]:
        return None
    return statistics.median(
        sum(v for k, v in r["obs"].items()
            if k.startswith("hash_bytes_total{")) for r in run["rounds"]
    ) / 1e9


# the per-layer readings this profile gives: each a median per round
READINGS = {
    "layer1_span_ms": lambda run: _median_ms(
        run, "layer1.contribute", "layer1.retract", "layer1.gc"),
    "contrib_meta_ms": lambda run: _median_ms(run, "engine.contrib_meta"),
    "host_hash_ms": lambda run: _median_ms(run, *HASH_SPANS),
    "hash_fetch_ms": lambda run: _median_ms(run, "hash.fetch"),
    "hashed_gb": _hashed_gb,
    "execute_wait_ms": lambda run: _median_ms(run, "engine.wait"),
}


# --------------------------------------------------------------- a run


def run(cell: dict, seed: int, seconds: float, *,
        check_device: bool = True, log=print) -> dict:
    """One traced run of `cell`; each round's record gains `spans`
    (seconds per span name) and `obs` (the change to the replicas'
    metrics), and `trace` the keys of `idle_by_span`."""
    import jax

    import repro.obs
    tracer_cls = repro.obs.Tracer
    tracers: list = []
    snapshots: List[Dict[str, float]] = []

    def make_tracer(*args, **kw):
        tracers.append(tracer_cls(
            *args, annotate=jax.profiler.TraceAnnotation, **kw))
        return tracers[-1]

    def snapshot(reps) -> None:
        snapshots.append(_summed([rep.metrics() for rep in reps.reps]))

    reset_stats, layer1 = harness.Replicas.reset_stats, \
        harness.Replicas.layer1

    def reset_then_snapshot(self):          # just before the window
        reset_stats(self)
        snapshot(self)

    def layer1_then_snapshot(self):         # once after each round
        out = layer1(self)
        snapshot(self)
        return out

    reduce_trace = trace_reduce.reduce_trace

    def reduce_with_program_spans(profile):
        out = reduce_trace(profile)
        named = idle_by_span(profile)
        return out if out is None or named is None else {**out, **named}

    with contextlib.ExitStack() as stack:
        for obj, name, new in (
                (repro.obs, "Tracer", make_tracer),
                (harness.Replicas, "reset_stats", reset_then_snapshot),
                (harness.Replicas, "layer1", layer1_then_snapshot),
                (trace_reduce, "reduce_trace", reduce_with_program_spans)):
            stack.enter_context(mock.patch.object(obj, name, new))
        rec = harness.run_cell(cell, seed, seconds, True,
                               t_start=time.perf_counter(),
                               check_device=check_device, log=log)
    per_round = spans_by_round(tracers[-1].spans)
    changes = [_changes(a, b) for a, b in zip(snapshots, snapshots[1:])]
    if not len(per_round) == len(changes) == len(rec["rounds"]):
        raise RuntimeError(
            f"{len(rec['rounds'])} rounds, but {len(per_round)} rounds of "
            f"spans and {len(changes)} of counters")
    for r, spans, obs in zip(rec["rounds"], per_round, changes):
        r["spans"], r["obs"] = spans, obs
    return rec


def _medians(dicts: List[Dict[str, float]], scale: float
             ) -> Dict[str, float]:
    names = sorted({k for d in dicts for k in d})
    return {n: scale * statistics.median(d.get(n, 0.0) for d in dicts)
            for n in names}


def report(bench: dict, workload: str, seed: int, rec: dict) -> dict:
    """The lines a run prints, and the JSON result it ends with."""
    rounds = rec["rounds"]
    print("spans: " + json.dumps(_medians([r["spans"] for r in rounds],
                                          1e3)), flush=True)
    print("obs: " + json.dumps(_medians([r["obs"] for r in rounds], 1.0)),
          flush=True)
    trace = rec["trace"] or {}
    print("idle: " + json.dumps({k: trace.get(k) for k in
                                 ("window_s", "idle_s", "idle_split")}),
          flush=True)
    print("gaps: " + json.dumps(trace.get("program_gaps")), flush=True)
    layer = {m["name"]: harness.load_reader(m["name"])(rec)
             for m in harness.metrics_for(bench, workload, True)}
    return {"workload": workload, "seed": seed, "correct": rec["correct"],
            "rounds": len(rounds),
            "merge_s_p50": harness.load_reader("merge_s_p50")(rec),
            "top_level_ms": _median_ms(rec, "top_level"),
            "readings": {n: f(rec) for n, f in READINGS.items()},
            "per_layer": layer,
            "busy_s": trace.get("busy_s"),
            "idle_gaps": trace.get("idle_gaps"),
            "device": rec["device"]}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.load_cell(args.workload, bench)
    print(f"compile cache: {harness.place_compile_cache()}", flush=True)
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        rec = run(cell, seed, args.seconds,
                  log=lambda s: print(s, flush=True))
        print(json.dumps(report(bench, args.workload, seed, rec)),
              flush=True)


if __name__ == "__main__":
    main()
