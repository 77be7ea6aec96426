"""Layer 2 — deterministic strategy execution (paper §4.3).

resolve(S, σ) = σ(sort_hash(Visible(S)), seed(MerkleRoot(S)))

Determinism mechanisms (paper Def. 6): (1) canonical ordering by content
hash; (2) seed derived from the Merkle root; (3) strategies are pure
functions. Binary-only strategies reduce via a sequential fold over the
canonical order (paper Remark 7) or, optionally, a balanced binary tree
(equalised influence, still deterministic — implemented as the paper's
suggested extension).

The canonical entry point is `resolve_spec(state, spec)` where `spec`
is a `repro.api.MergeSpec` — a frozen, validated, canonically-hashable
description of *what* to resolve (strategy + typed cfg + base ref +
reduction + trust threshold + hierarchical grouping). Every resolve
path — plain, trust-gated, hierarchical — funnels through one engine
pipeline (`_merge_ids`): planner keyed by per-tensor sub-roots,
leaf-at-a-time execution with bounded live memory, byte-budgeted cache,
leaf-granular fetch. `repro.api.Replica` is the ergonomic facade.

Legacy shims (all emit DeprecationWarning, all byte-identical to the
spec path they wrap):
  * `resolve(state, "ties", trim=0.3)`   -> resolve(state, MergeSpec(...))
  * `apply_strategy(name, contribs)`     -> reference_apply(...)
  * `hierarchical_resolve(states, name)` -> resolve over a grouped spec
  * `repro.core.trust.gated_resolve`     -> spec with trust_threshold

Beyond-paper L3 mitigations implemented here:
  * per-leaf resolve caching keyed by sub-root (byte-budgeted LRU,
    per-replica via `EngineCache` — `Replica.set_cache_limit`);
  * incremental resolve for strategies with algebraic structure
    (weight averaging: O(p) per new contribution);
  * hierarchical resolve (sub-group resolve + second pass), expressed
    as `MergeSpec(group_size=...)` so it shares the engine pipeline;
  * fetch-on-resolve: under a sharded blob store (repro.net.store) a
    replica's store holds only the payloads placed on it, so resolve
    accepts a `fetch` hook that pulls the missing visible payloads over
    the network on demand — determinism is unaffected because payloads
    are content-addressed (equal eid => byte-equal pytree, paper
    Assumption 11). The hook is leaf-granular: a plan whose every leaf
    task hits the cache (planner metadata is memoized by content id)
    completes WITHOUT fetching any payload at all, and payloads are
    pulled only when some leaf actually has to recompute.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.api.spec import coerce_spec, MergeSpec
from repro.core import engine
from repro.core.engine import (  # noqa: F401
    cache_info, CacheInfo, clear_cache, default_cache, EngineCache,
    reset_cache_limits, set_cache_limit)
from repro.core.merkle import merkle_root
from repro.core.state import CRDTMergeState
from repro.obs import layer1_timer, span
from repro.strategies import get_strategy

FetchHook = Callable[[Tuple[str, ...]], Dict[str, Any]]


def seed_from_root(root: bytes) -> int:
    """Strategy RNG seed derived from the Merkle root (paper Def. 6).

    >>> seed_from_root(b"\\x00" * 32)
    0
    >>> seed_from_root(b"\\xff" * 32) == 0x7FFFFFFFFFFFFFFF
    True
    """
    return int.from_bytes(root[:8], "big") & 0x7FFFFFFFFFFFFFFF


def canonical_order(state: CRDTMergeState) -> List[str]:
    return sorted(state.visible())


def _warn_shim(old: str, new: str) -> None:
    warnings.warn(f"{old} is deprecated; use {new}",
                  DeprecationWarning, stacklevel=3)


def _fetch_into(store: Dict[str, Any], absent: List[str],
                fetch: Optional[FetchHook]) -> Dict[str, Any]:
    """Pull `absent` payloads through the fetch hook into a copied store.
    Raises KeyError without a hook: silently merging a subset would be a
    wrong answer with no signal."""
    if fetch is None:
        raise KeyError(f"store lacks payloads for {list(absent)}; "
                       "sync blobs first or pass a fetch hook")
    store = dict(store)
    with span("engine.fetch", n=len(absent)):
        store.update(fetch(tuple(absent)))
    still = [i for i in absent if i not in store]
    if still:
        raise KeyError(f"fetch hook could not obtain {still}")
    return store


# ---------------------------------------------------------------------------
# The one engine pipeline every resolve path funnels through
# ---------------------------------------------------------------------------


def _merge_ids(store: Dict[str, Any], ids: List[str], spec: MergeSpec,
               seed: int, *, base: Any, fetch: Optional[FetchHook],
               cache: Optional[EngineCache], use_cache: bool,
               coverages: Optional[Dict[str, Optional[Tuple[str, ...]]]]
               = None) -> Tuple[Any, Dict[str, Any]]:
    """Merge the ordered id list through the planner/executor engine
    (whole-model strategies route through the legacy whole-tree path
    with a single cache entry). Returns (merged, store) — the store may
    have grown by fetched payloads, which grouped resolves reuse.

    `coverages` maps sparse element ids to their leaf coverage
    descriptors (from `CRDTMergeState.coverage()`); ids absent from the
    map (or mapped to None) are dense."""
    strat = get_strategy(spec.strategy)
    covs: Optional[List[Optional[Tuple[str, ...]]]] = None
    if coverages and any(coverages.get(i) is not None for i in ids):
        covs = [coverages.get(i) for i in ids]

    if strat.whole_model or strat.leaf_fn is None:
        # whole-tree route. The whole-model cache key is derivable from
        # the eids alone (a sparse payload's content hash determines its
        # densified form given the base, which the key also covers), so
        # probe it BEFORE fetching: a warm re-resolve on a blob-shedding
        # replica must not re-ship k full models for a result it
        # already has.
        if use_cache:
            key = engine.model_key(
                None, [bytes.fromhex(i) for i in ids],
                base=base, seed=seed, spec=spec)
            hit = engine.cache_lookup(key, cache)
            if hit is not None:
                return hit, store
        absent = [i for i in ids if i not in store]
        if absent:
            store = _fetch_into(store, absent, fetch)
        out = engine.merge([store[i] for i in ids], contrib_ids=tuple(ids),
                           base=base, seed=seed, use_cache=use_cache,
                           spec=spec, cache=cache, coverages=covs)
        return out, store

    # engine route: plan from resident payloads + memoized digests; the
    # hashes count on the registry of the cache's owner
    obs = (cache if cache is not None else default_cache()).obs
    metas = {}
    unknown = []
    for i in ids:
        if i in store:
            metas[i] = engine.contrib_meta(store[i], eid=i, obs=obs)
        else:
            m = engine.memoized_meta(i)
            if m is None:
                unknown.append(i)
            else:
                metas[i] = m
    if unknown:
        # never-seen contributions must be pulled just to plan. With
        # caching on, pull ONLY those: an updated fine-tune shares most
        # leaf digests with its retracted predecessor, so the other
        # absent payloads may turn out not to be needed at all. With
        # caching off every absent payload is certain to be needed —
        # combine both pulls into one hook round trip.
        need = unknown if use_cache else \
            [i for i in ids if i not in store]
        store = _fetch_into(store, need, fetch)
        for i in unknown:
            metas[i] = engine.contrib_meta(store[i], eid=i, obs=obs)
    plan = engine.plan_merge([metas[i] for i in ids], base=base,
                             seed=seed, spec=spec, coverages=covs, obs=obs)
    absent = [i for i in ids if i not in store]
    if absent:
        if use_cache:
            # leaf-granular AND fold-aware: pull only the payloads some
            # cache-missed task actually consumes, minus already-folded
            # prefixes — O(changed) fetch; an all-cached plan pulls
            # nothing at all.
            needed = engine.plan_needed_ids(plan, cache)
            pull = [ids[j] for j in needed if ids[j] not in store]
        else:
            pull = absent
        if pull:
            store = _fetch_into(store, pull, fetch)
    out = engine.execute_plan(plan, [store.get(i) for i in ids],
                              base=base, use_cache=use_cache, cache=cache)
    return out, store


def _grouped_resolve(store: Dict[str, Any], ids: List[str],
                     spec: MergeSpec, seed: int, *, base: Any,
                     fetch: Optional[FetchHook],
                     cache: Optional[EngineCache], use_cache: bool,
                     coverages: Optional[Dict[str, Optional[Tuple[str, ...]]]]
                     = None) -> Any:
    """Two-level resolve (paper §7.2 L3 mitigation 2): sub-groups of
    `spec.group_size` over the canonical order resolve first; a second
    pass merges the sub-group outputs with seed+1. Both passes run
    through the engine, so group outputs cache by sub-root and missing
    payloads fetch leaf-granularly per group. Sub-group outputs are
    dense whatever their inputs' coverage (absent leaves inherited the
    base), so the second pass never sees sparsity."""
    groups = [ids[i:i + spec.group_size]
              for i in range(0, len(ids), spec.group_size)]
    firsts = []
    for g in groups:
        out, store = _merge_ids(store, g, spec, seed, base=base,
                                fetch=fetch, cache=cache,
                                use_cache=use_cache, coverages=coverages)
        firsts.append(out)
    return engine.merge(firsts, base=base, seed=seed + 1,
                        use_cache=use_cache, spec=spec, cache=cache)


def resolve_spec(state: CRDTMergeState, spec: MergeSpec, *,
                 base: Any = None, trust: Any = None,
                 fetch: Optional[FetchHook] = None,
                 cache: Optional[EngineCache] = None,
                 use_cache: bool = True,
                 verify_base: bool = True) -> Any:
    """Compute the merged model the spec describes, over the state's
    converged visible set.

    `trust` is a `repro.core.trust.TrustState`; when the spec carries a
    `trust_threshold`, the visible set is deterministically gated at
    the Layer-2 boundary (evidence is a CRDT, so honest replicas gate
    identically) and the strategy seed derives from the Merkle root of
    the GATED id set — exactly the legacy `gated_resolve` seeding.

    `fetch` is the sharded-store hook: called with the visible eids
    whose payloads are actually needed and locally absent, it must
    return them (typically by pulling them over the network — repro.net
    installs a hook that runs multi-source chunk fetch against the
    placement's holders). Payloads are needed only for leaf tasks that
    miss the per-leaf cache: a warm re-resolve on a replica that has
    shed its blobs fetches nothing. Without a hook, a needed-but-missing
    payload raises KeyError.

    `cache` scopes the per-leaf/whole-model cache (None = the process
    default; `repro.api.Replica` passes its own).
    """
    if not isinstance(spec, MergeSpec):
        raise TypeError(f"resolve_spec() requires a MergeSpec, got "
                        f"{type(spec).__name__}")
    if spec.base_ref is not None:
        if base is None:
            raise KeyError(
                f"spec pins base_ref {spec.base_ref[:16]}… but no base "
                "payload was supplied; pass base= (or resolve through a "
                "Replica that registered it)")
        if verify_base:
            # the ref pins the base EXACTLY — two replicas resolving
            # the same gossiped spec must use byte-equal bases or the
            # determinism story silently breaks. Callers whose base
            # provably came from a digest-keyed registry (Replica's
            # base store) pass verify_base=False to skip the
            # full-model hash.
            from repro.api.spec import SpecError
            from repro.core.hashing import pytree_digest
            got = pytree_digest(base).hex()
            if got != spec.base_ref:
                raise SpecError(
                    f"base payload digest {got[:16]}… does not match "
                    f"the spec's base_ref {spec.base_ref[:16]}…")
    # Layer-1 slice of the resolve — visibility gate, canonical order,
    # Merkle root, seed derivation — timed into the overhead histogram
    # backing the paper's <0.5 ms claim (no-op clockless path when obs
    # is disabled).
    with layer1_timer():
        if spec.trust_threshold is not None:
            from repro.core.trust import TrustState, gated_visible
            t = trust if trust is not None else TrustState()
            ids = sorted(gated_visible(state, t, spec.trust_threshold))
            if not ids:
                raise ValueError("all contributions gated out")
            root = merkle_root([bytes.fromhex(i) for i in ids])
        else:
            ids = canonical_order(state)
            if not ids:
                raise ValueError(
                    "resolve() requires a non-empty visible set")
            root = state.merkle_root()
        seed = seed_from_root(root)
        coverages = state.coverage()
    if spec.group_size is not None:
        return _grouped_resolve(state.store, ids, spec, seed, base=base,
                                fetch=fetch, cache=cache,
                                use_cache=use_cache, coverages=coverages)
    out, _ = _merge_ids(state.store, ids, spec, seed, base=base,
                        fetch=fetch, cache=cache, use_cache=use_cache,
                        coverages=coverages)
    return out


def resolve(state: CRDTMergeState, spec: Any, base: Any = None, *,
            reduction: Optional[str] = None, use_cache: bool = True,
            fetch: Optional[FetchHook] = None,
            cache: Optional[EngineCache] = None,
            trust: Any = None, **cfg) -> Any:
    """Resolve the state. `spec` is a `repro.api.MergeSpec`.

    The historical form `resolve(state, "ties", trim=0.3)` still works
    but is DEPRECATED: it wraps the unvalidated kwargs in a lenient
    MergeSpec and delegates, emitting DeprecationWarning. Construct a
    MergeSpec instead — unknown or ill-typed cfg then fails at spec
    construction, and the spec's digest keys the engine cache.
    """
    if isinstance(spec, MergeSpec):
        return resolve_spec(state, coerce_spec(spec, cfg,
                                               reduction=reduction),
                            base=base, trust=trust, fetch=fetch,
                            cache=cache, use_cache=use_cache)
    _warn_shim("resolve(state, strategy_name, **cfg)",
               "resolve(state, MergeSpec(strategy, cfg)) or "
               "Replica.resolve(spec)")
    lenient = coerce_spec(spec, cfg, reduction=reduction, lenient=True)
    return resolve_spec(state, lenient, base=base, trust=trust,
                        fetch=fetch, cache=cache, use_cache=use_cache)


# ---------------------------------------------------------------------------
# Whole-tree reference path (Remark 16 transparency baseline)
# ---------------------------------------------------------------------------


def reference_apply(strategy_name: str, contribs: List[Any], *, base=None,
                    seed: int = 0, reduction: str = "fold", **cfg) -> Any:
    """Direct (non-CRDT) strategy application over an ORDERED list.

    This is exactly what Layer 2 invokes — the legacy whole-tree path,
    kept as the byte-for-byte reference for the Remark 16 transparency
    check and the engine equivalence suite. Not deprecated: it IS the
    definition the engine is verified against.
    """
    strat = get_strategy(strategy_name)
    if strat.binary_only and len(contribs) > 2:
        if reduction == "tree":
            return _tree_fold(strat, contribs, base, seed, cfg)
        return _seq_fold(strat, contribs, base, seed, cfg)
    return strat(contribs, base=base, seed=seed, **cfg)


def sparse_reference_apply(strategy_name: str, contribs: List[Any],
                           coverages: List[Optional[Tuple[str, ...]]], *,
                           base: Any, seed: int = 0,
                           reduction: str = "fold", **cfg) -> Any:
    """Reference semantics for mixed dense/sparse contribution lists,
    built ONLY from the whole-tree path: each model leaf is merged over
    exactly its covering contribution subset, at its global flatten
    index; zero-coverage leaves inherit the base.

    Implementation: group leaves by covering subset; for each distinct
    subset, densify its contributions (base fill) and run the dense
    `reference_apply` over the FULL model structure, then keep only the
    leaves whose covering subset it is. Leafwise strategies act
    per-leaf with the global flatten index, so those kept leaves are
    byte-exactly the per-leaf merge of that subset — an engine-free
    definition the sparse engine path is verified against."""
    strat = get_strategy(strategy_name)
    if strat.whole_model or strat.leaf_fn is None:
        dense = engine.densify_contributions(contribs, coverages, base)
        return reference_apply(strategy_name, dense, base=base, seed=seed,
                               reduction=reduction, **cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(base)
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    subset_of: Dict[str, Tuple[int, ...]] = {
        p: tuple(j for j, cov in enumerate(coverages)
                 if cov is None or p in cov) for p in paths}
    out = [None] * len(paths)
    for subset in set(subset_of.values()):
        if not subset:
            for i, p in enumerate(paths):
                if subset_of[p] == subset:
                    out[i] = flat[i][1]
            continue
        dense = engine.densify_contributions(
            [contribs[j] for j in subset],
            [coverages[j] for j in subset], base)
        ref = jax.tree_util.tree_leaves(reference_apply(
            strategy_name, dense, base=base, seed=seed,
            reduction=reduction, **cfg))
        for i, p in enumerate(paths):
            if subset_of[p] == subset:
                out[i] = ref[i]
    return jax.tree_util.tree_unflatten(treedef, out)


def apply_strategy(strategy_name: str, contribs: List[Any], *, base=None,
                   seed: int = 0, reduction: str = "fold", **cfg) -> Any:
    """DEPRECATED alias of `reference_apply` (the old public name)."""
    _warn_shim("apply_strategy()", "reference_apply() (byte-exact "
               "reference) or engine.merge(spec=MergeSpec(...)) "
               "(cached/planned execution)")
    return reference_apply(strategy_name, contribs, base=base, seed=seed,
                           reduction=reduction, **cfg)


def _seq_fold(strat, contribs, base, seed, cfg):
    acc = contribs[0]
    for i, c in enumerate(contribs[1:]):
        acc = strat([acc, c], base=base, seed=seed + i + 1, **cfg)
    return acc


def _tree_fold(strat, contribs, base, seed, cfg):
    """Balanced binary-tree reduction: depth ceil(log2 k), equal influence
    (paper Remark 7's suggested alternative)."""
    level = list(contribs)
    rnd = 0
    while len(level) > 1:
        nxt = []
        for i in range(0, len(level) - 1, 2):
            rnd += 1
            nxt.append(strat([level[i], level[i + 1]], base=base,
                             seed=seed + rnd, **cfg))
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    return level[0]


# ---------------------------------------------------------------------------
# Incremental resolve (paper §7.2 L3 mitigation 3)
# ---------------------------------------------------------------------------


class IncrementalMean:
    """O(p)-per-contribution running weight average.

    Matches weight_average over the same visible set because fp32 running
    sums are order-dependent only through accumulation order — so
    `sync()` re-folds in canonical order whenever out-of-order
    contributions arrive, and drops ids the state has since retracted.
    Fast path: appends.
    """

    def __init__(self):
        self._sum = None
        self._ids: List[str] = []

    def add(self, element_id: str, contribution) -> None:
        if self._sum is None:
            self._sum = jax.tree_util.tree_map(
                lambda x: jnp.asarray(x, jnp.float32), contribution)
        else:
            self._sum = jax.tree_util.tree_map(
                lambda a, x: a + x.astype(jnp.float32), self._sum,
                contribution)
        self._ids.append(element_id)

    def sync(self, state: CRDTMergeState) -> bool:
        """Re-fold from the state's canonical visible set.

        Brings the accumulator back in line with the resolved
        weight_average after out-of-order arrivals or retractions:
        retracted ids are dropped, missed ones folded in, and
        accumulation order restored to canonical. Returns True if a
        re-fold was needed (False = accumulator already canonical).
        Raises KeyError if a visible element's payload is absent from
        the store (resolve would fail there too) — silently averaging
        a subset would be a wrong answer with no signal."""
        ids = canonical_order(state)
        absent = [eid for eid in ids if eid not in state.store]
        if absent:
            raise KeyError(f"store lacks payloads for {absent}; "
                           "fetch missing blobs before sync()")
        if ids == self._ids:
            return False
        self._sum = None
        self._ids = []
        for eid in ids:
            self.add(eid, state.store[eid])
        return True

    def value(self):
        k = len(self._ids)
        if k == 0:
            raise ValueError("IncrementalMean has no contributions")
        return jax.tree_util.tree_map(lambda s: s / k, self._sum)

    def count(self) -> int:
        return len(self._ids)


def hierarchical_resolve(states: List[CRDTMergeState], spec: Any,
                         group_size: int = 8, base=None, *,
                         reduction: Optional[str] = None,
                         fetch: Optional[FetchHook] = None,
                         cache: Optional[EngineCache] = None,
                         use_cache: bool = True, **cfg):
    """Two-level resolve over the join of `states`: sub-groups resolve
    locally; a second pass merges sub-group outputs (paper §7.2 L3
    mitigation 2). Deterministic given the same partitioning policy
    (groups formed over the canonical order).

    `spec` is a MergeSpec (its `group_size` wins over the parameter;
    if unset, the parameter's grouping is applied). The historical form
    `hierarchical_resolve(states, "ties", group_size=4)` is DEPRECATED
    — it is exactly `resolve(merged_state, MergeSpec(..., group_size))`.
    """
    if not states:
        raise ValueError("hierarchical_resolve() requires >= 1 state")
    if isinstance(spec, MergeSpec):
        spec = coerce_spec(spec, cfg, reduction=reduction)
    else:
        _warn_shim("hierarchical_resolve(states, strategy_name, **cfg)",
                   "resolve(state, MergeSpec(strategy, cfg, "
                   "group_size=...))")
        spec = coerce_spec(spec, cfg, reduction=reduction, lenient=True)
    if spec.group_size is None:
        spec = spec.replace(group_size=group_size)
    merged = states[0]
    for s in states[1:]:
        merged = merged.merge(s)
    return resolve_spec(merged, spec, base=base, fetch=fetch, cache=cache,
                        use_cache=use_cache)
