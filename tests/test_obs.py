"""repro.obs: registry semantics, span tracing, SEC probes, exporters —
and the PR's inertness contract: tracing on never changes a merged
byte, and identical converged contribution sets produce identical
deterministic aggregates regardless of delivery order (20 orderings).
"""
import contextlib
import io
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import MergeSpec, Replica
from repro.core.gossip import GossipNetwork
from repro.net.simulator import SimGossipNetwork
from repro.net.wire import MESSAGE_TYPES
from repro.obs import (
    CATALOG, ConvergenceProbe, CounterView, default_registry, EventLog,
    layer1_timer, MetricsRegistry, set_enabled, set_tracer, span, to_events,
    Tracer, write_jsonl)
from repro.obs.probes import wire_phase, WIRE_PHASES
from repro.strategies import list_strategies


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Obs globals are process-wide; leave them as found."""
    prev_enabled = set_enabled(True)
    prev_tracer = set_tracer(None)
    default_registry().clear()
    yield
    default_registry().clear()
    set_tracer(prev_tracer)
    set_enabled(prev_enabled)


# ------------------------------------------------------------- registry


def test_counter_gauge_histogram_roundtrip():
    reg = MetricsRegistry()
    reg.counter("gossip_sends_total").inc()
    reg.counter("gossip_sends_total").inc(2)
    assert reg.counter("gossip_sends_total").value() == 3.0
    reg.gauge("net_queue_depth").set(7)
    reg.gauge("net_queue_depth").set(2)
    assert reg.gauge("net_queue_depth").value() == 2.0
    reg.gauge("engine_peak_stacked_bytes").set_max(10)
    reg.gauge("engine_peak_stacked_bytes").set_max(4)
    assert reg.gauge("engine_peak_stacked_bytes").value() == 10.0
    h = reg.histogram("resolve_layer1_overhead_ms")
    for v in (0.02, 0.04, 0.3):
        h.observe(v)
    assert h.count() == 3
    assert h.sum() == pytest.approx(0.36)
    assert h.quantile(1.0) == 0.3
    assert h.quantile(0.0) == 0.02


def test_undeclared_metric_name_raises():
    reg = MetricsRegistry()
    with pytest.raises(KeyError, match="not declared"):
        reg.counter("made_up_metric_total")


def test_kind_and_label_mismatches_raise():
    reg = MetricsRegistry()
    with pytest.raises(TypeError):
        reg.gauge("gossip_sends_total")          # declared as counter
    with pytest.raises(ValueError):
        reg.counter("engine_events_total").inc()  # missing event label
    with pytest.raises(ValueError):
        reg.counter("gossip_sends_total").inc(event="x")  # takes none
    with pytest.raises(ValueError):
        reg.counter("gossip_sends_total").inc(-1)  # counters go up


def test_snapshot_formats_labeled_series_and_histograms():
    reg = MetricsRegistry()
    reg.counter("engine_events_total").inc(3, event="hits")
    reg.histogram("probe_convergence_seconds").observe(0.002)
    snap = reg.snapshot()
    assert snap["engine_events_total{event=hits}"] == 3.0
    assert snap["probe_convergence_seconds_count"] == 1.0
    assert snap["probe_convergence_seconds_sum"] == pytest.approx(0.002)
    assert any(k.startswith("probe_convergence_seconds_bucket{le=")
               for k in snap)


def test_aggregate_is_exactly_the_deterministic_slice():
    reg = MetricsRegistry()
    reg.counter("engine_events_total").inc(event="dispatches")   # det
    reg.counter("sync_events_total").inc(event="syncs")          # not
    reg.gauge("probe_root_divergence").set(0)                    # det
    reg.gauge("net_queue_depth").set(5)                          # not
    aggr = reg.aggregate()
    assert set(aggr) == {"engine_events_total{event=dispatches}",
                         "probe_root_divergence"}


def test_merged_sums_counters_and_maxes_gauges():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("gossip_sends_total").inc(2)
    b.counter("gossip_sends_total").inc(3)
    a.gauge("net_queue_depth").set(1)
    b.gauge("net_queue_depth").set(9)
    merged = a.merged(b)
    assert merged["gossip_sends_total"] == 5.0
    assert merged["net_queue_depth"] == 9.0


def test_catalog_names_follow_scheme():
    for name, spec in CATALOG.items():
        assert spec.name == name
        assert spec.kind in ("counter", "gauge", "histogram")
        if spec.kind == "counter":
            assert name.endswith("_total"), name


# ---------------------------------------------------------- CounterView


def test_counter_view_behaves_like_a_stats_dict():
    reg = MetricsRegistry()
    stats = CounterView(reg, "sync_events_total")
    assert stats["syncs"] == 0                   # unseen key reads 0
    stats["syncs"] += 1
    stats["syncs"] += 2
    assert stats["syncs"] == 3
    assert isinstance(stats["syncs"], int)
    assert "syncs" in stats and "other" not in stats
    assert dict(stats) == {"syncs": 3}
    # the view IS the registry series
    assert reg.counter("sync_events_total").value(event="syncs") == 3.0
    with pytest.raises(ValueError):
        stats["syncs"] = 1                       # counters can't decrease
    stats.clear()
    assert len(stats) == 0


# --------------------------------------------------------------- tracer


def test_tracer_nesting_ids_and_clock():
    clk = iter(range(10))
    tr = Tracer(clock=clk.__next__, node="a")
    with tr.span("resolve", strategy="slerp"):
        with tr.span("plan") as sp:
            sp.set(leaves=3)
    assert [(s.name, s.t0, s.t1, s.parent_id) for s in tr.spans] == \
        [("plan", 1, 2, "s1"), ("resolve", 0, 3, None)]
    assert tr.spans[0].attrs == {"leaves": 3}
    ev = tr.spans[1].to_event()
    assert ev["kind"] == "span" and ev["id"] == "s1"


def test_module_span_routes_to_installed_tracer_only():
    with span("noop"):                           # no tracer: no-op
        pass
    tr = Tracer(clock=iter(range(10)).__next__)
    set_tracer(tr)
    with span("real", k=1):
        pass
    set_enabled(False)
    with span("disabled"):                       # disabled: no-op again
        pass
    set_enabled(True)
    assert [s.name for s in tr.spans] == ["real"]


def test_layer1_timer_respects_disabled_and_explicit_registry():
    set_enabled(False)
    with layer1_timer() as t:
        pass
    assert t.ms is None                          # clock never read
    reg = MetricsRegistry()
    with layer1_timer(reg) as t:                 # explicit scope wins
        pass
    assert t.ms is not None
    assert reg.histogram("resolve_layer1_overhead_ms").count() == 1


# ---------------------------------------------------------- wire phases


def test_every_wire_message_type_has_a_phase():
    for cls in MESSAGE_TYPES.values():
        assert wire_phase(cls.__name__) in WIRE_PHASES
    assert wire_phase("StateMsg") == "gossip"
    assert wire_phase("ChunkData") == "transfer"
    assert wire_phase("NoSuchMsg") == "control"


# -------------------------------------------------------------- probes


def test_convergence_probe_episode_and_straggler_flags():
    reg = MetricsRegistry()
    clk = iter(range(100))
    p = ConvergenceProbe(registry=reg, clock=clk.__next__)
    assert p.observe({"a": "r1", "b": "r1", "c": "r1"})
    assert not p.observe({"a": "r1", "b": "r1", "c": "r2"})
    assert p.diverged
    assert reg.gauge("probe_root_divergence").value() == 1.0
    # plurality is r1; c is the straggler
    assert reg.gauge("probe_replica_diverged").value(node="c") == 1.0
    assert reg.gauge("probe_replica_diverged").value(node="a") == 0.0
    assert p.observe({"a": "r2", "b": "r2", "c": "r2"})
    assert not p.diverged
    assert p.episodes == [(1, 2)]
    assert reg.histogram("probe_convergence_seconds").count() == 1


def test_convergence_probe_tie_break_is_deterministic():
    reg = MetricsRegistry()
    p = ConvergenceProbe(registry=reg, clock=iter(range(10)).__next__)
    p.observe({"a": "r9", "b": "r1"})            # tie: lower hex wins
    assert reg.gauge("probe_replica_diverged").value(node="b") == 0.0
    assert reg.gauge("probe_replica_diverged").value(node="a") == 1.0


# ------------------------------------------------------------ exporters


def test_event_log_verbosity_contract():
    for verbosity, expect in ((-1, ""), (0, "plain line\n")):
        stream = io.StringIO()
        log = EventLog(verbosity, stream=stream)
        log.emit("step", "plain line", k=1)
        assert stream.getvalue() == expect
        assert log.events[0]["event"] == "step"
    stream = io.StringIO()
    reg = MetricsRegistry()
    log = EventLog(1, registry=reg, stream=stream)
    log.emit("step", "plain line", k=1)
    ev = json.loads(stream.getvalue())
    assert ev == {"kind": "event", "event": "step",
                  "text": "plain line", "k": 1}
    assert reg.counter("launch_events_total").value(event="step") == 1.0


def test_jsonl_export_roundtrip(tmp_path):
    tr = Tracer(clock=iter(range(10)).__next__, node="a")
    with tr.span("x"):
        pass
    reg = MetricsRegistry()
    reg.counter("gossip_sends_total").inc(4)
    events = to_events(tracer=tr, registry=reg, meta={"seed": 1})
    path = tmp_path / "trace.jsonl"
    assert write_jsonl(str(path), events) == 3
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert lines[0] == {"kind": "meta", "node": "a", "seed": 1}
    assert lines[1]["kind"] == "span" and lines[1]["name"] == "x"
    assert lines[2] == {"kind": "metric", "name": "gossip_sends_total",
                        "value": 4.0}


# ------------------------------------------------- inertness (the claim)


def _contribs(k, shape=(8, 8), seed=3):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in range(k)]


class _RecordingAnnotate:
    """A fake `Tracer(annotate=...)` hook: counts what it annotates."""

    def __init__(self):
        self.entered = 0

    def __call__(self, name):
        self.entered += 1
        return contextlib.nullcontext()


@pytest.mark.parametrize("annotate", [None, _RecordingAnnotate],
                         ids=["no-hook", "recording-hook"])
def test_all_26_strategies_byte_identical_with_tracing_on(annotate):
    """Enabling spans + the Layer-1 timer, with or without an annotate
    hook, must not move a single output byte, across the full strategy
    catalog."""
    hook = annotate() if annotate is not None else None
    outs = {}
    spans_seen = 0
    for tracing in (False, True):
        set_enabled(tracing)
        tracer = Tracer(annotate=hook) if tracing else None
        set_tracer(tracer)
        rep = Replica("inert")
        for c in _contribs(4):
            rep.contribute(c)
        outs[tracing] = {
            s: np.asarray(rep.resolve(MergeSpec(s), use_cache=False)
                          ).tobytes()
            for s in list_strategies()}
        if tracer is not None:
            spans_seen = len(tracer.spans)
        set_tracer(None)
        set_enabled(True)
    assert len(outs[True]) == 26
    assert outs[True] == outs[False]
    assert spans_seen > 0                        # tracing actually ran
    if hook is not None:
        assert hook.entered == spans_seen


def test_20_orderings_identical_aggregates_and_bytes():
    """The SEC telemetry claim: across 20 gossip delivery orderings,
    every replica resolves to the same bytes AND reports the same
    deterministic metric aggregates — with tracing enabled."""
    set_tracer(Tracer())
    baseline = None
    for ordering in range(20):
        net = GossipNetwork(4, seed=ordering)    # seed = shuffle order
        for node, c in zip(net.nodes, _contribs(4, seed=99)):
            node.contribute(c)
        net.all_pairs_round()
        assert net.converged()
        for node in net.nodes:
            rep = Replica(node.node_id, state=node.state)
            out = np.asarray(rep.resolve(MergeSpec("slerp"))).tobytes()
            aggr = rep.metrics(deterministic_only=True)
            assert aggr                          # engine counters present
            if baseline is None:
                baseline = (out, aggr)
            assert (out, aggr) == baseline
    set_tracer(None)


def test_replica_metrics_and_trace_export(tmp_path):
    rep = Replica("exp")
    for c in _contribs(3):
        rep.contribute(c)
    rep.resolve(MergeSpec("weight_average"))
    m = rep.metrics()
    assert m["engine_events_total{event=dispatches}"] >= 1.0
    assert set(rep.metrics(deterministic_only=True)) <= set(m)
    path = tmp_path / "rep.jsonl"
    n = rep.trace_to(str(path))
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert len(lines) == n
    assert lines[0] == {"kind": "meta", "node": "exp"}
    assert {x["name"] for x in lines if x["kind"] == "metric"} == set(m)


def test_sim_clock_trace_is_byte_identical_across_runs(tmp_path):
    """Same seed + schedule on the simulator => the JSONL trace (spans
    on the virtual clock, probe episodes in virtual seconds) is
    byte-for-byte reproducible — what CI archives from bench_gossip."""
    def run(path):
        g = SimGossipNetwork(3, seed=7, mode="antientropy")
        payloads = _contribs(3, shape=(4, 4), seed=5)
        g.contribute_all(lambda i: {"w": payloads[i]})
        tracer = g.make_tracer(run="sec")
        probe = g.make_probe()
        set_tracer(tracer)
        try:
            assert not g.observe_convergence(probe)
            for _ in range(4):
                g.all_pairs_round()
                if g.observe_convergence(probe):
                    break
        finally:
            set_tracer(None)
        assert g.converged() and not probe.diverged
        write_jsonl(str(path), to_events(tracer=tracer, meta={"seed": 7}))
        return probe.episodes

    ep1 = run(tmp_path / "a.jsonl")
    ep2 = run(tmp_path / "b.jsonl")
    assert ep1 == ep2 and len(ep1) == 1
    assert (tmp_path / "a.jsonl").read_bytes() == \
        (tmp_path / "b.jsonl").read_bytes()
    assert any(json.loads(x)["kind"] == "span"
               for x in (tmp_path / "a.jsonl").read_text().splitlines())
