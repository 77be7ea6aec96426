"""The planner's metas seeded from the content id's leaf digests.

`Replica.contribute` hashes an arriving contribution once, for its
content id (`hashing.pytree_leaf_digests`), and memoizes the planner's
`ContribMeta` from those leaf digests (`engine.seed_meta`), so the
first resolve hashes nothing of it. The seeded meta has to be the one
`engine.contrib_meta` would have hashed, field by field, whatever the
leaves' flatten order against their sorted path order; a resolve from
it has to give the same bytes and the same leaf sub-roots; and where
no digests were computed (a caller-supplied id) or they describe other
values (a quantized payload) the planner hashes as before.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import MergeSpec, Replica
from repro.core import engine
from repro.core.compression import (
    compress_tree, compressed_tree_to_structure)
from repro.core.engine import clear_meta_memo, contrib_meta, seed_meta
from repro.core.hashing import (
    leaf_paths_of, pytree_digest, pytree_leaf_digests)


@pytest.fixture(autouse=True)
def _clean():
    clear_meta_memo()
    yield
    clear_meta_memo()


def _arr(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _dense(seed):
    rng = np.random.default_rng(seed)
    return {"emb": _arr(rng, (16, 8), jnp.float32),
            "blocks": {"w": _arr(rng, (8, 8), jnp.float32),
                       "b": _arr(rng, (8,), jnp.float32)}}


def _mixed(seed):
    rng = np.random.default_rng(seed)
    return {"w": _arr(rng, (8, 6), jnp.bfloat16),
            "norm": _arr(rng, (6,), jnp.float32),
            "head": _arr(rng, (6, 4), jnp.bfloat16)}


def _shuffled(seed):
    """A nested tree whose sorted `keystr` order is not its flatten
    order: a list of 11 leaves ("[10]" sorts before "[2]") and a key
    "a#" whose path sorts before "['a']..." (`#` < `'`)."""
    rng = np.random.default_rng(seed)
    return {"a": {"z": _arr(rng, (3,), jnp.float32),
                  "y": [_arr(rng, (2, i + 1), jnp.float32)
                        for i in range(11)]},
            "a#": _arr(rng, (4,), jnp.bfloat16),
            "b": _arr(rng, (5,), jnp.float32)}


def _sparse(seed):
    rng = np.random.default_rng(seed)
    return {"blocks": {"w": _arr(rng, (8, 8), jnp.float32)}}


def _flatten_order_differs(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    return paths != sorted(paths)


def _assert_meta_equal(seeded, hashed):
    assert seeded.treedef == hashed.treedef
    assert seeded.digests == hashed.digests
    assert seeded.shapes == hashed.shapes
    assert seeded.dtypes == hashed.dtypes
    assert seeded.paths == hashed.paths
    assert seeded.scales == hashed.scales
    assert seeded == hashed


def _sources(rep):
    return {k.split("=", 1)[1].rstrip("}"): v
            for k, v in rep.metrics().items()
            if k.startswith("contrib_meta_total{")}


def _hashed(rep):
    return {k.split("=", 1)[1].rstrip("}"): v
            for k, v in rep.metrics().items()
            if k.startswith("hash_bytes_total{")}


TREES = {"dense": _dense, "sparse": _sparse, "mixed": _mixed,
         "shuffled": _shuffled}


@pytest.mark.parametrize("kind", sorted(TREES))
def test_seeded_meta_equals_hashed(kind):
    tree = TREES[kind](1)
    if kind == "shuffled":
        assert _flatten_order_differs(tree)
    rep = Replica("seed")
    leaves = leaf_paths_of(tree) if kind == "sparse" else None
    eid = rep.contribute(tree, leaves=leaves)
    assert eid == pytree_digest(tree).hex()
    seeded = engine.memoized_meta(eid)
    assert seeded is not None
    clear_meta_memo()
    hashed = contrib_meta(tree, eid=eid)
    _assert_meta_equal(seeded, hashed)
    assert _sources(rep) == {"content_id": 1.0}


def test_leaf_digests_give_the_content_id():
    tree = _shuffled(2)
    digest, leaf_digests = pytree_leaf_digests(tree)
    assert digest == pytree_digest(tree)
    meta = contrib_meta(tree)
    assert leaf_digests == dict(zip(meta.paths, meta.digests))


def test_seed_refuses_digests_it_cannot_match():
    tree = _dense(3)
    eid = pytree_digest(tree).hex()
    _, leaf_digests = pytree_leaf_digests(tree)
    short = dict(list(leaf_digests.items())[1:])
    assert seed_meta(tree, eid, short) is None
    extra = dict(leaf_digests, **{"['x']": b"\x00" * 32})
    assert seed_meta(tree, eid, extra) is None
    ct = compress_tree(tree)
    assert seed_meta(ct, eid, leaf_digests) is None
    assert seed_meta(compressed_tree_to_structure(ct), eid,
                     leaf_digests) is None
    assert engine.memoized_meta(eid) is None


def _record_plans(monkeypatch):
    plans = []
    real = engine.plan_merge

    def recording(*a, **k):
        plan = real(*a, **k)
        plans.append(plan)
        return plan
    monkeypatch.setattr(engine, "plan_merge", recording)
    return plans


def _bitwise_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


RESOLVES = {
    "weight_average": (False, False),
    "ties": (True, False),
    "task_arithmetic": (True, True),
}


@pytest.mark.parametrize("strategy", sorted(RESOLVES))
def test_resolve_from_seeded_meta_matches_hashed(strategy, monkeypatch):
    pin_base, sparse = RESOLVES[strategy]
    rep = Replica("resolve")
    spec = MergeSpec(strategy)
    if pin_base:
        spec = MergeSpec(strategy, base_ref=rep.register_base(_dense(0)))
    for j in range(1, 4):
        if sparse:
            c = _sparse(j) if j % 2 else {"emb": _dense(j)["emb"]}
            rep.contribute(c, leaves=leaf_paths_of(c))
        else:
            rep.contribute(_dense(j))
    assert _sources(rep) == {"content_id": 3.0}
    plans = _record_plans(monkeypatch)
    seeded = jax.block_until_ready(rep.resolve(spec, use_cache=False))
    assert _sources(rep) == {"content_id": 3.0}
    assert "contrib_meta" not in _hashed(rep)
    clear_meta_memo()
    hashed = jax.block_until_ready(rep.resolve(spec, use_cache=False))
    assert _sources(rep) == {"content_id": 3.0, "hashed": 3.0}
    _bitwise_equal(seeded, hashed)
    first, second = plans
    assert [t.sub_root for t in first.tasks] == \
        [t.sub_root for t in second.tasks]
    assert [t.digests for t in first.tasks] == \
        [t.digests for t in second.tasks]


def test_caller_supplied_id_is_hashed():
    rep = Replica("given")
    eid = "ab" * 32
    rep.contribute(_dense(4), element_id=eid)
    assert engine.memoized_meta(eid) is None
    assert _sources(rep) == {}
    rep.resolve(MergeSpec("weight_average"))
    assert _sources(rep) == {"hashed": 1.0}
    assert _hashed(rep) == {"contrib_meta": sum(
        int(x.nbytes) for x in jax.tree_util.tree_leaves(_dense(4)))}


@pytest.mark.parametrize("form", ["structure", "tree"])
def test_compressed_contribution_is_hashed(form):
    """A quantized payload's planner digests describe its dequantized
    values, the content id's do not: the planner hashes it. (A whole
    `CompressedTree` is not a pytree, so it comes with its id.)"""
    rep = Replica("quant")
    ct = compress_tree(_dense(5))
    if form == "tree":
        eid = rep.contribute(ct, element_id="cd" * 32)
    else:
        eid = rep.contribute(compressed_tree_to_structure(ct))
    assert engine.memoized_meta(eid) is None
    assert _sources(rep) == {}
    rep.resolve(MergeSpec("weight_average"))
    assert _sources(rep) == {"hashed": 1.0}
    meta = engine.memoized_meta(eid)
    assert meta.scales is not None and all(
        s is not None for s in meta.scales)


def test_window_round_counts():
    """A sliding-window round: one arrival seeded from its content id,
    nothing hashed by the planner; a repeated arrival of known content
    is a memo hit and counts nothing."""
    rep = Replica("window")
    spec = MergeSpec("weight_average")
    eids = [rep.contribute(_mixed(j)) for j in range(1, 4)]
    rep.resolve(spec)
    assert _sources(rep) == {"content_id": 3.0}
    for r in range(2):
        before = _sources(rep)
        eids.append(rep.contribute(_mixed(10 + r)))
        rep.retract(eids.pop(0))
        rep.resolve(spec)
        after = _sources(rep)
        assert after["content_id"] - before["content_id"] == 1.0
        assert "hashed" not in after
    rep.contribute(_mixed(10))
    assert _sources(rep) == {"content_id": 5.0}
    assert "contrib_meta" not in _hashed(rep)
