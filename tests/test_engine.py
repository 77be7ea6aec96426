"""Planner/executor merge engine: byte-for-byte legacy equivalence for
all 26 strategies, per-leaf incremental re-merge, ordering convergence,
byte-budgeted caching, leaf-granular fetch, and the fused batch path."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_contribs

from repro.api import MergeSpec
from repro.core import engine
from repro.core.compression import compress_tree, decompress_tree
from repro.core.properties import controlled_tensors
from repro.core.resolve import (
    cache_info, canonical_order, clear_cache, hierarchical_resolve,
    reference_apply, reset_cache_limits, resolve, seed_from_root,
    set_cache_limit)
from repro.core.state import CRDTMergeState
from repro.strategies import get_strategy, list_strategies


def _bytes_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))


def _ctrl_eid(prefix: str) -> str:
    """Hex eid with a controlled 2-hex-digit sort prefix, so tests can
    pin a contribution's canonical-order position."""
    return prefix + hashlib.sha256(prefix.encode()).hexdigest()[:62]


def _pytree_contribs(k=3, seed=0):
    rng = np.random.default_rng(seed)

    def tree():
        return {"emb": jnp.asarray(rng.standard_normal((6, 4)), jnp.float32),
                "ln": jnp.asarray(rng.standard_normal((4,)), jnp.float32),
                "blk": {"w": jnp.asarray(rng.standard_normal((4, 4)),
                                         jnp.float32)}}
    return [tree() for _ in range(k)], tree()


@pytest.fixture(scope="module")
def x64():
    with jax.enable_x64(True):
        yield


@pytest.fixture(scope="module")
def grid(x64):
    """The tier-1 4x4 float64 grid (same tensors as the algebraic audit)."""
    return controlled_tensors(4, dtype=jnp.float64)


# ------------------------------------------------------- equivalence ---


@pytest.mark.parametrize("name", sorted(list_strategies()))
@pytest.mark.parametrize("reduction", ["fold", "tree"])
def test_engine_matches_legacy_on_tier1_grid(name, reduction, grid):
    """Engine output is byte-identical to the legacy whole-tree path for
    every registry strategy under both reductions (paper Def. 6
    transparency, now across the planner/executor split)."""
    legacy = reference_apply(name, grid, seed=123, reduction=reduction)
    eng = engine.merge(grid, name, seed=123, reduction=reduction,
                       use_cache=False)
    assert _bytes_equal(legacy, eng), name


@pytest.mark.parametrize("name", sorted(list_strategies()))
def test_engine_matches_legacy_on_pytrees_with_base(name):
    """Mixed-shape pytree + explicit base: exercises batched same-dtype
    dispatches, per-leaf folds, and global-leaf-index key derivation."""
    contribs, base = _pytree_contribs(k=3, seed=7)
    legacy = reference_apply(name, contribs, base=base, seed=99)
    eng = engine.merge(contribs, name, base=base, seed=99, use_cache=False)
    assert _bytes_equal(legacy, eng), name


def test_resolve_routes_through_engine_byte_identical():
    """resolve() (engine path) == apply_strategy on the canonically
    ordered contributions with the Merkle-derived seed."""
    contribs, _ = _pytree_contribs(k=4, seed=3)
    s = CRDTMergeState()
    for i, c in enumerate(contribs):
        s = s.add(c, node=f"n{i}")
    ids = canonical_order(s)
    ordered = [s.store[i] for i in ids]
    seed = seed_from_root(s.merkle_root())
    for name in ("weight_average", "ties", "dare", "slerp",
                 "genetic_merge", "star", "evolutionary_merge"):
        wrapped = resolve(s, MergeSpec(name), use_cache=False)
        direct = reference_apply(name, ordered, seed=seed)
        assert _bytes_equal(wrapped, direct), name


def test_convergence_20_orderings_through_engine():
    """20 insertion/merge orderings of the same contribution set resolve
    to byte-identical outputs through the engine (no caching assist)."""
    contribs, _ = _pytree_contribs(k=5, seed=11)
    rng = np.random.default_rng(0)
    reference = None
    for trial in range(20):
        order = rng.permutation(len(contribs))
        states = []
        for j in order:
            st = CRDTMergeState()
            states.append(st.add(contribs[int(j)], node=f"n{int(j)}"))
        merged = states[0]
        for st in states[1:]:
            merged = merged.merge(st)
        out = resolve(merged, MergeSpec("ties"), use_cache=False)
        if reference is None:
            reference = out
        else:
            assert _bytes_equal(reference, out), f"ordering {trial}"


# ------------------------------------------------------- incremental ---


def _leafy_model(seed, n_leaves=12, bump=()):
    r = np.random.default_rng(seed)
    t = {f"l{i:02d}": jnp.asarray(r.standard_normal((8, 8)), jnp.float32)
         for i in range(n_leaves)}
    for i in bump:
        t[f"l{i:02d}"] = t[f"l{i:02d}"] + 0.5
    return t


def test_incremental_resolve_only_changed_leaves_recompute():
    """After an updated contribution (retract + re-add, 3 of 12 tensors
    changed, canonical position pinned), re-resolve executes exactly the
    3 changed leaf tasks — the other 9 hit the per-leaf cache even
    though the whole-model Merkle root changed."""
    clear_cache()
    s = CRDTMergeState()
    for j, p in enumerate(["aa", "bb", "cc"]):
        s = s.add(_leafy_model(j), node=f"n{j}", element_id=_ctrl_eid(p))
    resolve(s, MergeSpec("ties"))
    s2 = s.remove(_ctrl_eid("cc"), "n2").add(
        _leafy_model(2, bump=(0, 5, 7)), node="n2",
        element_id=_ctrl_eid("cd"))          # still sorts last
    assert s2.merkle_root() != s.merkle_root()
    engine.reset_exec_stats()
    out = resolve(s2, MergeSpec("ties"))
    stats = engine.exec_stats()
    assert stats["leaf_tasks"] == 3
    assert stats["hits"] == 9 and stats["misses"] == 3
    legacy = reference_apply(
        "ties", [s2.store[i] for i in canonical_order(s2)],
        seed=seed_from_root(s2.merkle_root()))
    assert _bytes_equal(out, legacy)
    clear_cache()


def test_stochastic_strategies_do_not_reuse_stale_leaves():
    """Key-consuming strategies derive leaf randomness from the Merkle
    seed, so their sub-roots include it: a changed visible set must
    recompute EVERY leaf (a per-leaf hit would replay stale masks)."""
    clear_cache()
    s = CRDTMergeState()
    for j, p in enumerate(["aa", "bb", "cc"]):
        s = s.add(_leafy_model(j, n_leaves=4), node=f"n{j}",
                  element_id=_ctrl_eid(p))
    resolve(s, MergeSpec("dare"))
    s2 = s.remove(_ctrl_eid("cc"), "n2").add(
        _leafy_model(2, n_leaves=4, bump=(0,)), node="n2",
        element_id=_ctrl_eid("cd"))
    engine.reset_exec_stats()
    out = resolve(s2, MergeSpec("dare"))
    assert engine.exec_stats()["leaf_tasks"] == 4      # no stale reuse
    legacy = reference_apply(
        "dare", [s2.store[i] for i in canonical_order(s2)],
        seed=seed_from_root(s2.merkle_root()))
    assert _bytes_equal(out, legacy)
    clear_cache()


# ---------------------------------------------------- cache behaviour ---


def test_cache_byte_budget_eviction():
    """Size-aware eviction: resident bytes never exceed the budget, the
    LRU tensor goes first, and an evicted leaf recomputes to identical
    bytes. Uses a non-incremental strategy so each entry costs exactly
    one leaf's bytes (incremental strategies cache their fp32 fold
    accumulator alongside the value — covered below)."""
    clear_cache()
    leaf_bytes = 8 * 8 * 4
    set_cache_limit(bytes=5 * leaf_bytes)     # room for 5 of 12 leaves
    try:
        s = CRDTMergeState()
        for j in range(3):
            s = s.add(_leafy_model(j), node=f"n{j}")
        out1 = resolve(s, MergeSpec("ties"))
        info = cache_info()
        assert info.entries == 5
        assert info.bytes == 5 * leaf_bytes
        assert info.bytes <= info.byte_limit
        out2 = resolve(s, MergeSpec("ties"))   # 5 hits + 7 recomputes
        assert _bytes_equal(out1, out2)
    finally:
        reset_cache_limits()
        clear_cache()


def test_cache_budget_counts_fold_accumulators():
    """Incremental strategies cache (value, fp32 accumulator) per leaf;
    the byte budget accounts both, so fewer entries fit."""
    clear_cache()
    leaf_bytes = 8 * 8 * 4
    entry_bytes = 2 * leaf_bytes              # fp32 value + fp32 acc
    set_cache_limit(bytes=5 * leaf_bytes)
    try:
        s = CRDTMergeState()
        for j in range(3):
            s = s.add(_leafy_model(j), node=f"n{j}")
        out1 = resolve(s, MergeSpec("weight_average"))
        info = cache_info()
        assert info.entries == 2              # 2 * 512B <= 1280B < 3 * 512B
        assert info.bytes == 2 * entry_bytes
        out2 = resolve(s, MergeSpec("weight_average"))
        assert _bytes_equal(out1, out2)
    finally:
        reset_cache_limits()
        clear_cache()


def test_cache_single_entry_larger_than_budget_not_retained():
    clear_cache()
    set_cache_limit(bytes=10)                 # smaller than any leaf
    try:
        s = CRDTMergeState()
        for j in range(2):
            s = s.add(_leafy_model(j, n_leaves=2), node=f"n{j}")
        resolve(s, MergeSpec("weight_average"))
        assert cache_info().entries == 0
        assert cache_info().bytes == 0
    finally:
        reset_cache_limits()
        clear_cache()


def test_whole_model_strategy_gets_single_cached_entry():
    clear_cache()
    contribs, _ = _pytree_contribs(k=3, seed=5)
    s = CRDTMergeState()
    for i, c in enumerate(contribs):
        s = s.add(c, node=f"n{i}")
    r1 = resolve(s, MergeSpec("genetic_merge"))
    assert cache_info().entries == 1          # one whole-model entry
    r2 = resolve(s, MergeSpec("genetic_merge"))
    assert r2 is r1                           # identical cached tree
    clear_cache()


# ------------------------------------------------- leaf-granular fetch ---


def test_resolve_fetches_nothing_when_fully_cached():
    """Warm cache + memoized planner metadata: a replica that shed every
    payload still resolves, without calling the fetch hook at all."""
    clear_cache()
    s = CRDTMergeState()
    for j in range(3):
        s = s.add(_leafy_model(j), node=f"n{j}")
    warm = resolve(s, MergeSpec("ties"))
    bare = CRDTMergeState(s.adds, s.removes, s.vv, {})   # all blobs shed
    calls = []

    def hook(eids):
        calls.append(eids)
        return {e: s.store[e] for e in eids}

    out = resolve(bare, MergeSpec("ties"), fetch=hook)
    assert calls == []
    assert _bytes_equal(out, warm)
    # without a hook it also succeeds — nothing is needed
    assert _bytes_equal(resolve(bare, MergeSpec("ties")), warm)
    clear_cache()


def test_whole_model_warm_resolve_fetches_nothing():
    """Regression: the whole-model cache key is derivable from the eids
    alone, so a warm re-resolve of a whole_model strategy on a replica
    that shed its blobs must hit the cache WITHOUT re-shipping k full
    models."""
    clear_cache()
    s = CRDTMergeState()
    for j in range(3):
        s = s.add(_leafy_model(j, n_leaves=3), node=f"n{j}")
    warm = resolve(s, MergeSpec("star"))
    bare = CRDTMergeState(s.adds, s.removes, s.vv, {})
    calls = []

    def hook(eids):
        calls.append(eids)
        return {e: s.store[e] for e in eids}

    out = resolve(bare, MergeSpec("star"), fetch=hook)
    assert calls == []
    assert out is warm                    # the cached whole-model tree
    clear_cache()


def test_resolve_fetches_only_when_leaves_miss():
    """Cold cache: the absent payloads ARE needed and must be pulled
    (and a hookless resolve must still KeyError)."""
    clear_cache()
    s = CRDTMergeState()
    for j in range(3):
        s = s.add(_leafy_model(j), node=f"n{j}")
    victim = canonical_order(s)[0]
    payload = s.store[victim]
    bare = CRDTMergeState(s.adds, s.removes, s.vv,
                          {e: p for e, p in s.store.items() if e != victim})
    with pytest.raises(KeyError):
        resolve(bare, MergeSpec("ties"))
    calls = []

    def hook(eids):
        calls.append(eids)
        return {victim: payload}

    out = resolve(bare, MergeSpec("ties"), fetch=hook)
    assert calls == [(victim,)]
    assert _bytes_equal(out, resolve(s, MergeSpec("ties"), use_cache=False))
    clear_cache()


# ------------------------------------------------------- misc contract ---


def test_empty_contributions_raise_value_error():
    """Survives `python -O`: misuse raises ValueError, not AssertionError."""
    with pytest.raises(ValueError):
        get_strategy("weight_average")([])
    with pytest.raises(ValueError):
        engine.merge([], "weight_average")
    with pytest.raises(ValueError):
        engine.plan_merge([], "weight_average")


def test_plan_rejects_mismatched_structures():
    a = {"w": jnp.ones((2, 2))}
    b = {"w": jnp.ones((3, 3))}
    with pytest.raises(ValueError):
        engine.plan_for([a, b], "weight_average")


def test_execute_plan_without_payloads_requires_full_cache():
    clear_cache()
    contribs = [{"w": jnp.ones((2, 2))}, {"w": jnp.zeros((2, 2))}]
    plan = engine.plan_for(contribs, "weight_average")
    with pytest.raises(KeyError):
        engine.execute_plan(plan, None)
    engine.execute_plan(plan, contribs)           # populate
    out = engine.execute_plan(plan, None)         # now payload-free
    assert float(out["w"][0, 0]) == 0.5
    clear_cache()


def test_bounded_peak_stacked_bytes():
    """The executor never stacks more than ~2 leaves' worth of slices;
    the legacy path stacks k full model copies."""
    contribs = [_leafy_model(j, n_leaves=20) for j in range(4)]
    engine.reset_exec_stats()
    engine.merge(contribs, "weight_average", use_cache=False)
    stats = engine.exec_stats()
    leaf_stacked = 4 * 8 * 8 * 4
    assert stats["peak_stacked_bytes"] <= 2 * leaf_stacked
    legacy_stacked = 4 * 20 * 8 * 8 * 4           # k x full model
    assert stats["peak_stacked_bytes"] * 5 <= legacy_stacked


def test_hierarchical_resolve_honors_fetch_and_reduction():
    clear_cache()
    contribs = make_contribs(12, seed=21)   # 4 sub-groups: fold != tree
    states = [CRDTMergeState().add(c, node=f"n{i}")
              for i, c in enumerate(contribs)]
    fold = hierarchical_resolve(states, MergeSpec("slerp"), group_size=3)
    tree = hierarchical_resolve(
        states, MergeSpec("slerp", reduction="tree"), group_size=3)
    assert not _bytes_equal(fold, tree)           # reduction= is honored
    with pytest.warns(DeprecationWarning):        # string-form shim
        legacy_tree = hierarchical_resolve(states, "slerp", group_size=3,
                                           reduction="tree")
    assert _bytes_equal(tree, legacy_tree)
    # sharded store: one payload lives elsewhere -> fetch= pulls it.
    # Hierarchical passes now cache by sub-root, so drop the warm cache
    # first: a cached group output would (correctly) resolve with zero
    # fetches, which is its own test below.
    victim_state = states[0]
    eid = canonical_order(victim_state)[0]
    payload = victim_state.store[eid]
    states[0] = CRDTMergeState(victim_state.adds, victim_state.removes,
                               victim_state.vv, {})
    warm = hierarchical_resolve(states, MergeSpec("slerp"), group_size=3)
    assert _bytes_equal(warm, fold)     # cache-complete: no payload need
    clear_cache()
    with pytest.raises(KeyError):
        hierarchical_resolve(states, MergeSpec("slerp"), group_size=3)
    calls = []

    def hook(eids):
        calls.append(eids)
        return {eid: payload}

    fetched = hierarchical_resolve(states, MergeSpec("slerp"), group_size=3,
                                   fetch=hook)
    assert calls == [(eid,)]
    assert _bytes_equal(fetched, fold)
    clear_cache()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name,kw", [
    ("weight_average", {}), ("linear", {"t": 0.3}),
    ("task_arithmetic", {"lam": 0.7}), ("negative_merge", {})])
def test_fused_linear_batch_exact_and_cached(name, kw, dtype):
    """The linear family fuses a pytree's same-dtype leaves into one
    batch whose output is bitwise the reference's, and every output of
    it is cached: a second merge on the same cache dispatches nothing."""
    contribs, base = _pytree_contribs(k=4, seed=13)
    contribs, base = jax.tree_util.tree_map(
        lambda x: x.astype(dtype), (contribs, base))
    cache = engine.EngineCache()
    got = engine.merge(contribs, name, base=base, cache=cache, **kw)
    stats = engine.exec_stats(cache)
    assert stats["batched_leaves"] > 0, name
    assert _bytes_equal(got, reference_apply(name, contribs, base=base,
                                             **kw)), name
    n_leaves = len(jax.tree_util.tree_leaves(base))
    again = engine.merge(contribs, name, base=base, cache=cache, **kw)
    after = engine.exec_stats(cache)
    assert after["hits"] - stats.get("hits", 0) == n_leaves
    assert after["dispatches"] == stats["dispatches"]
    assert _bytes_equal(again, got)


def test_engine_ties_histogram_trim_exact_and_cached():
    """TIES with the histogram trim runs the engine's one exact path:
    bitwise the reference, and cached."""
    contribs, base = _pytree_contribs(k=3, seed=19)
    kw = dict(base=base, trim_method="histogram")
    cache = engine.EngineCache()
    got = engine.merge(contribs, "ties", cache=cache, **kw)
    assert _bytes_equal(got, reference_apply("ties", contribs, **kw))
    dispatches = engine.exec_stats(cache)["dispatches"]
    again = engine.merge(contribs, "ties", cache=cache, **kw)
    assert engine.exec_stats(cache)["hits"] == \
        len(jax.tree_util.tree_leaves(base))
    assert engine.exec_stats(cache)["dispatches"] == dispatches
    assert _bytes_equal(again, got)


def test_engine_quantized_contributions_match_dequantized():
    """int8 contributions (`CompressedTree`) merge bitwise equal to
    their `decompress_tree` copies: each slice is densified where it is
    read (`dequant_leaves` counts them), and the output is cached."""
    contribs, base = _pytree_contribs(k=3, seed=23)
    cts = [compress_tree(c) for c in contribs]
    cache = engine.EngineCache()
    got = engine.merge(cts, "weight_average", cache=cache)
    n_leaves = len(jax.tree_util.tree_leaves(base))
    assert engine.exec_stats(cache)["dequant_leaves"] == 3 * n_leaves
    want = engine.merge([decompress_tree(c) for c in cts],
                        "weight_average", cache=engine.EngineCache())
    assert _bytes_equal(got, want)
    again = engine.merge(cts, "weight_average", cache=cache)
    assert engine.exec_stats(cache)["hits"] == n_leaves
    assert engine.exec_stats(cache)["dequant_leaves"] == 3 * n_leaves
    assert _bytes_equal(again, got)


def test_syncnode_resolve_counts_blob_pulls():
    """SyncNode.resolve pulls blobs through the hook only when a leaf
    task actually needs them (leaf-granular fetch accounting)."""
    from repro.net.antientropy import SyncNode
    clear_cache()
    s = CRDTMergeState()
    for j in range(2):
        s = s.add(_leafy_model(j, n_leaves=3), node=f"n{j}")
    full_store = dict(s.store)
    node = SyncNode("replica",
                    state=CRDTMergeState(s.adds, s.removes, s.vv, {}))
    node.fetch_hook = lambda _n, eids: {e: full_store[e] for e in eids}
    cold = node.resolve(MergeSpec("ties"))
    assert node.stats["resolve_blob_pulls"] == 2
    # payloads were fetched transiently, not retained: a warm re-resolve
    # of the same state needs nothing
    warm = node.resolve(MergeSpec("ties"))
    assert node.stats["resolve_blob_pulls"] == 2      # unchanged
    assert _bytes_equal(cold, warm)
    clear_cache()
