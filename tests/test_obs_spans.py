"""The program's spans and hash counter along one merge round.

A round is what a replica does when a fine-tune arrives: contribute it,
retract the oldest, collect the tombstone, resolve. Each step is
spanned where the work happens (Layer 1, the planner's hashes, the
executor's waits); the arrival is hashed once, for its content id,
on the digest pool's threads (which open no spans), and the planner
takes its leaf digests. Each host hash counts its bytes under
`hash_bytes_total{site}` on the replica's registry, and a tracer with
an `annotate` hook mirrors every span onto another clock in nesting
order.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.api import MergeSpec, Replica
from repro.core.engine import clear_meta_memo
from repro.obs import set_enabled, set_tracer, span, Tracer

K = 3


@pytest.fixture(autouse=True)
def _clean():
    prev_enabled = set_enabled(True)
    prev_tracer = set_tracer(None)
    clear_meta_memo()
    yield
    clear_meta_memo()
    set_tracer(prev_tracer)
    set_enabled(prev_enabled)


class Recorder:
    """A fake `annotate`: logs each annotation's enter and exit."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class Note:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))
        return Note()


def _model(seed):
    rng = np.random.default_rng(seed)
    return {"blocks": {"w": jnp.asarray(rng.standard_normal((8, 6)),
                                        jnp.bfloat16),
                       "norm": jnp.asarray(rng.standard_normal(6),
                                           jnp.float32)},
            "embed": jnp.asarray(rng.standard_normal((16, 4)),
                                 jnp.bfloat16)}


def _nbytes(tree):
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))


def _hashed(rep, metric="hash_bytes_total"):
    return {k.split("=", 1)[1].rstrip("}"): v
            for k, v in rep.metrics().items()
            if k.startswith(metric + "{")}


def _delta(after, before):
    return {s: after[s] - before.get(s, 0.0) for s in after
            if after[s] != before.get(s, 0.0)}


def _setup(strategy, pin_base):
    """A replica holding K contributions, resolved once (so their
    planner digests are memoized), and the spec a round resolves."""
    rep = Replica("spans")
    spec = MergeSpec(strategy)
    if pin_base:
        spec = MergeSpec(strategy, base_ref=rep.register_base(_model(0)))
    eids = [rep.contribute(_model(j)) for j in range(1, K + 1)]
    rep.resolve(spec)
    return rep, spec, eids


def _round(rep, spec, eids, c):
    eid = rep.contribute(c)
    rep.retract(eids.pop(0))
    eids.append(eid)
    state = rep.state
    rep.state = state.gc_tombstones(state.removes)
    return jax.block_until_ready(rep.resolve(spec))


ROUNDS = [("weight_average", False), ("ties", True)]


@pytest.mark.parametrize("strategy,pin_base", ROUNDS)
def test_round_spans_names_and_parents(strategy, pin_base):
    rep, spec, eids = _setup(strategy, pin_base)
    pooled = _hashed(rep, "hash_pooled_leaves_total")
    tracer = Tracer()
    set_tracer(tracer)
    c = _model(99)
    _round(rep, spec, eids, c)
    set_tracer(None)
    by_id = {s.span_id: s for s in tracer.spans}

    def parent(s):
        return by_id[s.parent_id].name if s.parent_id else None

    top = [s.name for s in tracer.spans if s.parent_id is None]
    want = ["layer1.contribute", "layer1.retract", "layer1.gc",
            "engine.plan", "engine.execute"]
    assert top == want
    named = {}
    for s in tracer.spans:
        named.setdefault(s.name, []).append(s)
    assert "engine.contrib_meta" not in named
    (digest,) = named["layer1.digest"]
    assert parent(digest) == "layer1.contribute"
    eid = named["layer1.contribute"][0].attrs["eid"]
    assert digest.attrs["eid"] == eid == eids[-1][:16]
    assert digest.attrs["bytes"] == _nbytes(c)
    assert named["layer1.retract"][0].attrs["eid"] != eid
    assert named["layer1.gc"][0].attrs == {"n": 1}
    n_leaves = len(jax.tree_util.tree_leaves(c))
    # the arrival's leaves are hashed on the digest pool, whose threads
    # open no spans; only tensor_digest's callers (the base) emit hash.*
    assert digest.attrs["leaves"] == n_leaves
    assert digest.attrs["workers"] == min(n_leaves, os.cpu_count() or 1)
    hashes = [s for s in tracer.spans if s.name.startswith("hash.")]
    if pin_base:
        (base,) = named["engine.base_digest"]
        assert parent(base) == "engine.plan"
        assert base.attrs["bytes"] == _nbytes(_model(0))
        assert {parent(s) for s in hashes} == {"engine.base_digest"}
        assert len(named["hash.fetch"]) == n_leaves
        assert len(named["hash.sha256"]) == n_leaves
    else:
        assert "engine.base_digest" not in named
        assert hashes == []
    # on a multi-core host each leaf of the arrival counts once
    assert _delta(_hashed(rep, "hash_pooled_leaves_total"), pooled) == (
        {"contribute": n_leaves} if (os.cpu_count() or 1) > 1 else {})
    waits = named["engine.wait"]
    assert {parent(s) for s in waits} == {"engine.execute"}
    assert sum(s.attrs["leaves"] for s in waits) == n_leaves


@pytest.mark.parametrize("strategy,pin_base", ROUNDS)
def test_hash_bytes_counted_per_site(strategy, pin_base):
    """A round hashes the arriving contribution once (its content id,
    whose leaf digests the planner takes) and, with a pinned base, the
    base once more; a second resolve of the same set hashes only the
    base."""
    rep, spec, eids = _setup(strategy, pin_base)
    c = _model(99)
    n = _nbytes(c)
    before = _hashed(rep)
    _round(rep, spec, eids, c)
    after = _hashed(rep)
    want = {"contribute": n}
    if pin_base:
        want["base"] = n
    assert _delta(after, before) == want
    assert sum(want.values()) == (2 if pin_base else 1) * n
    rep.resolve(spec, use_cache=False)
    assert _delta(_hashed(rep), after) == ({"base": n} if pin_base else {})


def test_register_base_counts_its_bytes():
    rep = Replica("base")
    base = _model(0)
    rep.register_base(base)
    assert _hashed(rep) == {"register_base": _nbytes(base)}


def test_annotate_follows_span_nesting():
    rec = Recorder()
    set_tracer(Tracer(annotate=rec))
    with span("outer", k=1):
        with span("inner"):
            pass
        with span("second"):
            pass
    assert rec.log == [("enter", "outer"), ("enter", "inner"),
                       ("exit", "inner"), ("enter", "second"),
                       ("exit", "second"), ("exit", "outer")]


def test_annotate_leaves_on_error():
    rec = Recorder()
    tracer = Tracer(annotate=rec)
    set_tracer(tracer)
    with pytest.raises(KeyError):
        with span("failing"):
            raise KeyError("x")
    assert rec.log == [("enter", "failing"), ("exit", "failing")]
    assert tracer.spans[0].attrs == {"error": "KeyError"}


def test_annotate_not_called_without_a_tracer():
    rec = Recorder()
    tracer = Tracer(annotate=rec)          # built, never installed
    rep, spec, eids = _setup("ties", True)
    _round(rep, spec, eids, _model(99))
    set_enabled(False)
    set_tracer(tracer)                     # installed, obs disabled
    _round(rep, spec, eids, _model(98))
    assert rec.log == [] and tracer.spans == []


def test_round_spans_annotated_in_nesting_order():
    """Every span of a real round is annotated, each enter matched by
    the exit of the same name in last-in first-out order."""
    rec = Recorder()
    tracer = Tracer(annotate=rec)
    rep, spec, eids = _setup("ties", True)
    set_tracer(tracer)
    _round(rep, spec, eids, _model(99))
    set_tracer(None)
    stack = []
    for kind, name in rec.log:
        if kind == "enter":
            stack.append(name)
        else:
            assert stack.pop() == name
    assert stack == []
    assert len(rec.log) == 2 * len(tracer.spans)


def test_obs_imports_no_jax():
    """The hook is handed in: importing `repro.obs` imports no JAX."""
    code = "import sys, repro.obs; sys.exit('jax' in sys.modules)"
    env = dict(os.environ, PYTHONPATH=str(Path(repro.__file__).parents[1]))
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=60).returncode == 0
