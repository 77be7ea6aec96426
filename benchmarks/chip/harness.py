"""One run of one cell: set-up, a window of merge rounds, the check.

Everything that belongs to a configuration, a traffic mix or a metric is
read from files found by name (`configs/`, `traffic/`, `metrics/`), so
a new cell, mix or metric is a new file and no edit here.

A round, as a user of the system waits for it: a fine-tune arrives at a
replica, and the replica holds the merged model of the new visible set
in device memory. One client, closed loop:

  (outside the round's clock) contribution r is generated on the device
  from (seed, r);
  1. `Replica.contribute(c_r)`;
  2. `Replica.retract(oldest)`;
  3. the retracted element's tombstone is collected (every replica has
     seen the retraction, so every tag is stable), so its payload
     leaves the store;
  4. `Replica.resolve(spec)`, then `jax.block_until_ready`.

The traffic mix (`traffic/<mix>.json`) says how a round is built:

  strategy, cfg   the MergeSpec resolved;
  k               contributions visible after set-up (all dense);
  pin_base        pin the base with `register_base` / `base_ref`;
  delta_std       the spread of a fine-tune around the base;
  retract         (default true) steps 2 and 3, so that k stay visible;
                  false: append only, the visible set grows a round;
  leaves          (default null: dense) the leaf names (last key of a
                  leaf's path) that each round's contribution carries:
                  a sparse contribution, `contribute(..., leaves=...)`;
  replica         (default "memory") one in-memory `Replica`;
                  "durable": `Replica(path=...)` in a directory under
                  $TMPDIR, journaling every operation; "sync_pair": two
                  replicas attached to `SyncNode`s over one persistent
                  loopback transport: the contribution arrives at the
                  first, an anti-entropy session carries it to the
                  second (span `sync`), and the round ends when both
                  hold the merged model;
  limits          the limit of each number the check compares.

Set-up generates the base and the first k contributions from the seed,
contributes them, pins the base where the mix asks, and runs one
warm-up round, so that nothing compiles inside the window.

The check (after the window, once the peak memory is read and the
replicas are freed) compares what the timed path produced with the
plain reference in `reference.py`: Layer 1 (the visible set, the Merkle
root and the content ids) and Layer 2 (the merged pytree of every
replica) of the last round and of one earlier round drawn from the seed.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import random
import sys
import tempfile
import time
from collections import deque
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
SPAN_PREFIX = "bench."
SAMPLED_ROUNDS = 3          # the earlier checked round is one of 1..3


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench: Optional[dict] = None) -> dict:
    """The workload entry `name` with its configuration and traffic."""
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(by_name)}")
    wl = by_name[name]
    entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    return {"name": name, "chips": wl["chips"],
            "config": load_json(ROOT / entry["file"]),
            "traffic": load_traffic(wl["traffic"])}


TRAFFIC_REQUIRED = {"strategy", "cfg", "k", "pin_base", "delta_std",
                    "limits"}
TRAFFIC_DEFAULTS = {"about": "", "retract": True, "leaves": None,
                    "replica": "memory"}
LIMITS = ("layer1_mismatches", "merged_gap")


def load_traffic(mix: str) -> dict:
    """`traffic/<mix>.json` with its defaults filled in. A key the
    harness does not know, a missing limit or one that is not a number
    is an error, so that a mix never runs another shape than it says."""
    traffic = load_json(BENCH_DIR / "traffic" / f"{mix}.json")
    return check_traffic(traffic, mix)


def check_traffic(traffic: dict, mix: str = "traffic") -> dict:
    missing = TRAFFIC_REQUIRED - set(traffic)
    unknown = set(traffic) - TRAFFIC_REQUIRED - set(TRAFFIC_DEFAULTS)
    if missing or unknown:
        raise ValueError(f"{mix}: missing keys {sorted(missing)}, unknown "
                         f"keys {sorted(unknown)}")
    limits = traffic["limits"]
    if set(limits) != set(LIMITS) or not all(
            isinstance(v, (int, float)) and not isinstance(v, bool)
            for v in limits.values()):
        raise ValueError(f"{mix}: limits must give a number for each of "
                         f"{LIMITS}, got {limits}")
    if traffic.get("replica", "memory") not in REPLICA_KINDS:
        raise ValueError(f"{mix}: replica must be one of "
                         f"{sorted(REPLICA_KINDS)}")
    return {**TRAFFIC_DEFAULTS, **traffic}


def metrics_for(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of `cell` reports: its end-to-end metrics, or
    with `trace` its per-layer ones (those that list the cell, or that
    move an end-to-end metric the cell reports)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def load_reader(name: str) -> Callable[[dict], Optional[float]]:
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------- weights


def root_key(seed: int):
    """PRNG key of a whole number of any size (31 bits at a time)."""
    import jax
    if seed < 0:
        raise SystemExit(f"--seed must be a whole number >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    rest = seed >> 31
    while rest:
        key = jax.random.fold_in(key, rest & 0x7FFFFFFF)
        rest >>= 31
    return key


class Weights:
    """Base and contributions of one configuration, made on the device
    from the seed: one jitted call for the base, one per contribution
    (the base plus `delta_std` times a standard normal, per leaf; leaf
    i of the model draws from key i whichever leaves a contribution
    carries)."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax
        import jax.numpy as jnp
        self.dtype = jnp.dtype(config["dtype"])
        leaves = config["leaves"]
        std, delta = config["init_std"], traffic["delta_std"]
        dtype = self.dtype

        def nest(paths, values):
            tree: dict = {}
            for path, v in zip(paths, values):
                node = tree
                for key in path[:-1]:
                    node = node.setdefault(key, {})
                node[path[-1]] = v
            return tree

        def gen_base(key):
            out = []
            for i, leaf in enumerate(leaves):
                shape = tuple(leaf["shape"])
                if leaf["init"] == "normal":
                    x = std * jax.random.normal(jax.random.fold_in(key, i),
                                                shape, jnp.float32)
                elif leaf["init"] == "ones":
                    x = jnp.ones(shape, jnp.float32)
                else:
                    x = jnp.zeros(shape, jnp.float32)
                out.append(x.astype(dtype))
            return nest([leaf["path"] for leaf in leaves], out)

        def gen_contribution(base_leaves, key, idx):
            return [(b.astype(jnp.float32) + delta * jax.random.normal(
                        jax.random.fold_in(key, i), b.shape, jnp.float32)
                     ).astype(dtype) for i, b in zip(idx, base_leaves)]

        key = root_key(seed)
        self._contrib_key = jax.random.fold_in(key, 1)
        self._gen_contribution = jax.jit(gen_contribution,
                                         static_argnums=2)
        self.base = jax.block_until_ready(
            jax.jit(gen_base)(jax.random.fold_in(key, 0)))
        flat, self._treedef = jax.tree_util.tree_flatten_with_path(
            self.base)
        self._names = [p[-1].key for p, _ in flat]
        self._paths = [[k.key for k in p] for p, _ in flat]
        self._base_leaves = [x for _, x in flat]
        self._nest = nest
        self.params = sum(int(x.size) for x in self._base_leaves)
        self.itemsize = dtype.itemsize

    def covered(self, names: Optional[List[str]]) -> tuple:
        """Indices of the model's leaves that a contribution carrying
        `names` holds (every leaf for None)."""
        if names is None:
            return tuple(range(len(self._names)))
        unknown = set(names) - set(self._names)
        if unknown:
            raise ValueError(f"no leaf named {sorted(unknown)}; the model "
                             f"has {sorted(set(self._names))}")
        return tuple(i for i, n in enumerate(self._names) if n in names)

    def covered_params(self, names: Optional[List[str]]) -> int:
        return sum(int(self._base_leaves[i].size)
                   for i in self.covered(names))

    def contribution(self, j: int, names: Optional[List[str]] = None):
        """Contribution j: dense, or with `names` the partial pytree of
        the leaves so named."""
        import jax
        idx = self.covered(names)
        out = self._gen_contribution([self._base_leaves[i] for i in idx],
                                     jax.random.fold_in(self._contrib_key,
                                                        j), idx)
        tree = (jax.tree_util.tree_unflatten(self._treedef, out)
                if names is None else
                self._nest([self._paths[i] for i in idx], out))
        return jax.block_until_ready(tree)


# ---------------------------------------------------------------- clocks


class CompileLog:
    """Seconds and count of XLA backend compiles, from JAX's events."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


COMPILES = CompileLog()
_LISTENING: List[bool] = []


def listen_for_compiles() -> CompileLog:
    if not _LISTENING:
        import jax
        jax.monitoring.register_event_duration_secs_listener(COMPILES)
        _LISTENING.append(True)
    return COMPILES


class Spans:
    """The benchmark's own spans: host-clock seconds of each named step
    of the current round, and, in a traced run, the same intervals as
    `jax.profiler.TraceAnnotation`s on the device trace's clock."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.current: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        ann = (jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
               if self.annotate else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.current[name] = self.current.get(name, 0.0) \
            + time.perf_counter() - t0

    def take(self) -> Dict[str, float]:
        out, self.current = self.current, {}
        return out


# ---------------------------------------------------------------- device


def device_info(chips: int, check_device: bool) -> dict:
    """Platform, kind and count of the devices the cell uses. A run
    that finds no TPU, fewer chips than the cell asks for, or Pallas in
    interpret mode, stops here and prints no result."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if check_device:
        if d.platform != "tpu":
            raise SystemExit(f"no TPU: JAX found platform {d.platform!r}")
        if len(devs) < chips:
            raise SystemExit(f"the cell asks for {chips} chips, JAX found "
                             f"{len(devs)}")
        from repro.kernels.config import kernel_env
        if kernel_env.resolve_interpret():
            raise SystemExit("Pallas kernels would run in interpret mode")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def place_compile_cache() -> str:
    """The program's persistent compile cache (`$JAX_COMPILATION_CACHE_DIR`
    or `<checkout>/.jax_cache`), keeping every program, however quick to
    compile: only the first run of a cell in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import place_compile_cache as place
    cache_dir = place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


def memory_peak_bytes(chips: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


# -------------------------------------------------------------- replicas


class Replicas:
    """The replicas a round runs on, of the kind the traffic mix names
    (see the module's docstring). A contribution arrives at the first;
    every replica resolves."""

    def __init__(self, kind: str):
        from repro.api import Replica
        self.kind = kind
        self._dir = None
        self._transport = None
        if kind == "memory":
            self.reps = [Replica("bench")]
        elif kind == "durable":
            self._dir = tempfile.TemporaryDirectory(prefix="bench-replica-")
            self.reps = [Replica("bench", path=self._dir.name)]
        else:
            from repro.net import PersistentLoopbackTransport, SyncNode
            self._transport = PersistentLoopbackTransport()
            self._nodes = {n: SyncNode(n) for n in ("a", "b")}
            for n in self._nodes:
                self._transport.register(n)
            self.reps = [Replica(n).attach(node)
                         for n, node in self._nodes.items()]

    def contribute(self, c, leaves: Optional[List[str]]) -> str:
        return self.reps[0].contribute(c, leaves=leaves)

    def retract(self, eid: str) -> None:
        self.reps[0].retract(eid)

    def sync(self) -> None:
        """Carry the first replica's state and payloads to the others
        (an anti-entropy session over the transport, run to quiet)."""
        if self._transport is None:
            return
        from repro.net import pump
        self._transport.send("a", "b", self._nodes["a"].begin_sync("b"))
        pump(self._nodes, self._transport)

    def gc(self) -> None:
        """Collect every tombstone: each replica has seen each
        retraction by now, so every tag is stable."""
        for rep in self.reps:
            state = rep.state
            rep.state = state.gc_tombstones(state.removes)

    def register_base(self, base) -> str:
        refs = {rep.register_base(base) for rep in self.reps}
        return refs.pop()

    def resolve(self, spec) -> list:
        import jax
        return [jax.block_until_ready(rep.resolve(spec))
                for rep in self.reps]

    def layer1(self) -> list:
        """(visible set, Merkle root) of each replica."""
        return [(rep.visible(), rep.merkle_root()) for rep in self.reps]

    def stats(self) -> dict:
        caches = [rep.cache for rep in self.reps]
        return {"hits": sum(c.stats["hits"] for c in caches),
                "misses": sum(c.stats["misses"] for c in caches),
                "peak_stacked_bytes": max(c.peak_stacked for c in caches)}

    def reset_stats(self) -> None:
        for rep in self.reps:
            rep.cache.reset_exec_stats()

    def close(self) -> None:
        for rep in self.reps:
            rep.close()
        self.reps = []
        if self._transport is not None:
            self._transport.close()
        if self._dir is not None:
            self._dir.cleanup()


REPLICA_KINDS = ("memory", "durable", "sync_pair")


# -------------------------------------------------------------- the check


def check_round(strategy: str, cfg: dict, contribs: Dict[int, object],
                eids: Dict[int, str], base, got: list) -> dict:
    """Compare one round's merged pytrees (one a replica) with the plain
    reference.

    `contribs` maps round index to the contribution, `eids` to the id
    the replica gave it. Returns the count of ids that differ from the
    reference content ids and the widest gap of any merged leaf (inf
    where a replica's model lacks a leaf or has one too many)."""
    import jax
    import reference
    ids = {j: reference.content_id(c) for j, c in contribs.items()}
    id_mismatches = sum(ids[j] != eids[j] for j in contribs)
    order = sorted(contribs, key=lambda j: ids[j])
    got_by = [dict(reference.leaves_with_paths(g)) for g in got]
    gap, seen = 0.0, set()
    for path, want in reference.merged_leaves(
            strategy, [contribs[j] for j in order], base, cfg):
        seen.add(path)
        for g in got_by:
            gap = max(gap, float(reference.leaf_gap(
                jax.numpy.asarray(g[path]), want))
                if path in g else float("inf"))
        del want
    if any(set(g) != seen for g in got_by):
        gap = float("inf")
    return {"id_mismatches": id_mismatches, "gap": gap}


# ------------------------------------------------------------------ a run


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, check_device: bool = True,
             log: Callable[[str], None] = print) -> dict:
    """One run: set-up, window, check. Returns the result record that
    the metric readers and the result line are built from."""
    sys.path.insert(0, str(BENCH_DIR))
    import jax

    import reference
    from repro.api import MergeSpec
    from repro.obs import set_tracer, Tracer

    compiles = listen_for_compiles()
    device = device_info(cell["chips"], check_device)
    from work import peaks_for
    peaks = peaks_for(device["kind"]) if check_device else None
    t_jax = time.perf_counter()
    c0, n0 = compiles.seconds, compiles.count
    traffic, config = check_traffic(cell["traffic"]), cell["config"]
    k, strategy = traffic["k"], traffic["strategy"]
    cfg = dict(traffic["cfg"])
    names = traffic["leaves"]

    # ------------------------------------------------------------ set-up
    weights = Weights(config, traffic, seed)
    ring = deque((j, weights.contribution(j)) for j in range(k))
    t_gen = time.perf_counter()
    reps = Replicas(traffic["replica"])
    ring = deque((j, reps.contribute(c, None), c) for j, c in ring)
    base = weights.base if traffic["pin_base"] else None
    spec = MergeSpec(strategy, cfg)
    if base is not None:
        spec = MergeSpec(strategy, cfg, base_ref=reps.register_base(base))
    t_contrib = time.perf_counter()
    spans = Spans(annotate=trace)

    def merge_round(j: int):
        with spans("generate"):
            c = weights.contribution(j, names)
        leaves = (None if names is None
                  else [p for p, _ in reference.leaves_with_paths(c)])
        t0 = time.perf_counter()
        with spans("contribute"):
            ring.append((j, reps.contribute(c, leaves), c))
        if traffic["retract"]:
            with spans("retract"):
                reps.retract(ring.popleft()[1])
        if reps.kind == "sync_pair":
            with spans("sync"):
                reps.sync()
        if traffic["retract"]:
            with spans("gc"):
                reps.gc()
        with spans("resolve"):
            outs = reps.resolve(spec)
        return outs, time.perf_counter() - t0

    outs, _ = merge_round(k)                           # warm-up round
    spans.take()
    t_warm = time.perf_counter()
    setup = {"jax_start_s": t_jax - t_start, "generate_s": t_gen - t_jax,
             "contribute_s": t_contrib - t_gen,
             "warmup_round_s": t_warm - t_contrib,
             "compile_s": compiles.seconds - c0,
             "compiles": compiles.count - n0,
             "peak_bytes_after_setup": memory_peak_bytes(cell["chips"])}
    log("setup: " + json.dumps(setup))

    # ------------------------------------------------------------ window
    sample = random.Random(seed).randint(1, SAMPLED_ROUNDS)
    sampled = None
    rounds: List[dict] = []
    layer1_bad: List[int] = []          # rounds whose Layer 1 was wrong
    reps.reset_stats()
    tracer = Tracer() if trace else None
    prev_tracer = set_tracer(tracer) if trace else None
    trace_dir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
    c_w, n_w = compiles.seconds, compiles.count
    setup_s = time.perf_counter() - t_start
    t_w0 = time.perf_counter()
    with (jax.profiler.TraceAnnotation(SPAN_PREFIX + "window") if trace
          else contextlib.nullcontext()):
        while True:
            r = len(rounds) + 1
            n_spans = len(tracer.spans) if trace else 0
            # the previous models are dropped once the new ones are
            # resident
            outs, round_s = merge_round(k + r)
            eids = [e for _, e, _ in ring]
            with spans("check"):
                root = reference.merkle_root(eids)
                if any(vis != frozenset(eids) or got_root != root
                       for vis, got_root in reps.layer1()):
                    layer1_bad.append(r)
            if r == sample:
                with spans("sample"):
                    sampled = {"js": {j: e for j, e, _ in ring},
                               "outs": jax.device_get(outs)}
            rec = {"round_s": round_s, **{f"{n}_s": v for n, v
                                          in spans.take().items()}}
            if trace:
                new = tracer.spans[n_spans:]
                for name in ("engine.plan", "engine.execute"):
                    rec[name.split(".")[1] + "_s"] = sum(
                        s.duration for s in new if s.name == name)
            rec["peak_bytes"] = memory_peak_bytes(cell["chips"])
            rounds.append(rec)
            if time.perf_counter() - t_w0 >= seconds:
                break
    window_s = time.perf_counter() - t_w0
    window_compiles = (compiles.count - n_w, compiles.seconds - c_w)
    busy = None
    if trace:
        jax.profiler.stop_trace()
        set_tracer(prev_tracer)
        import trace_reduce
        from jax.profiler import ProfileData
        with trace_dir:
            pb = sorted(Path(trace_dir.name).rglob("*.xplane.pb"))
            busy = trace_reduce.reduce_trace(ProfileData.from_file(
                str(pb[-1]))) if pb else None
    shares = {n: sum(x.get(f"{n}_s", 0.0) for x in rounds)
              for n in ("generate", "check", "sample")}
    peaks_seen = [setup["peak_bytes_after_setup"]] + [
        x["peak_bytes"] for x in rounds]
    log(f"window: {len(rounds)} rounds in {window_s:.6f} s; "
        + "; ".join(f"{n} {v:.6f} s ({100 * v / window_s:.3f}% of the "
                    f"window)" for n, v in shares.items())
        + f"; {window_compiles[0]} compiles ({window_compiles[1]:.6f} s) "
        f"inside the window; round seconds "
        f"{[round(x['round_s'], 6) for x in rounds]}; peak bytes in use "
        f"after set-up and after each round {peaks_seen}")

    peak = memory_peak_bytes(cell["chips"])
    counters = reps.stats()
    n_replicas = len(reps.reps)
    final = {j: e for j, e, _ in ring}
    final_contribs = {j: c for j, _, c in ring}
    reps.close()
    del reps, ring, spec
    gc.collect()

    # ------------------------------------------------------------- check
    t_check = time.perf_counter()
    checked = {len(rounds): check_round(strategy, cfg, final_contribs,
                                        final, base, outs)}
    del outs
    if sampled is not None and sampled["js"] != final:
        contribs = {j: (final_contribs[j] if j in final_contribs
                        else weights.contribution(j, None if j < k
                                                  else names))
                    for j in sampled["js"]}
        checked[sample] = check_round(strategy, cfg, contribs,
                                      sampled["js"], base, sampled["outs"])
        del contribs
    log(f"check: rounds {sorted(checked)} compared with the reference "
        f"in {time.perf_counter() - t_check:.6f} s")

    limits = traffic["limits"]
    checks = {
        "layer1_mismatches": {
            "value": len(layer1_bad) + sum(c["id_mismatches"]
                                           for c in checked.values()),
            "limit": limits["layer1_mismatches"]},
        "merged_gap": {"value": max(c["gap"] for c in checked.values()),
                       "limit": limits["merged_gap"]}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    failed = set(layer1_bad) | {
        r for r, c in checked.items()
        if c["id_mismatches"] or c["gap"] > limits["merged_gap"]}
    return {"cell": cell["name"], "device": device, "params":
            weights.params, "itemsize": weights.itemsize, "k": k,
            "round_params": weights.covered_params(names),
            "replicas": n_replicas,
            "setup": setup, "setup_s": setup_s, "window_s": window_s,
            "rounds": rounds, "memory_peak_bytes": peak,
            "counters": counters, "trace": busy, "peaks": peaks,
            "correct": correct,
            "failed": len(failed), "checks": checks}
