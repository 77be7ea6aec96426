"""Planner/executor merge engine — tensor-sharded Layer 2 execution.

The legacy Layer-2 path (`Strategy.__call__`) stacks k full model copies
per resolve and recomputes every tensor whenever anything in the visible
set changes. This module splits execution into:

  * a **planner** that walks the canonical contribution set and emits one
    `LeafTask` per model tensor, keyed by a per-tensor **sub-root** — the
    hash of that leaf's ordered contribution digests plus everything else
    that shapes the output (strategy, cfg, base leaf, fold structure, and
    the Merkle-derived seed where the strategy actually consumes it);
  * an **executor** that runs the plan leaf-by-leaf with bounded live
    memory (at most ~2 leaves' worth of stacked slices at a time),
    batching same-dtype elementwise leaves into fused dispatches;
  * a byte-budgeted **per-leaf cache** keyed by sub-root, so an unchanged
    tensor is a cache hit even when the whole-model Merkle root changed.

Determinism (paper Def. 6) is preserved by construction: the planner
uses the same canonical contribution order as the legacy path, and the
executor derives per-leaf randomness exactly as `strategies.base.leafwise`
does today — `fold_in(PRNGKey(seed & 0x7FFFFFFF), leaf_index)` with the
*global* flatten index. `tests/test_engine.py` verifies byte-for-byte
equality against the legacy path for all 26 registry strategies under
both fold and tree reductions.

Strategies flagged `whole_model=True` (population search and SVD-based
factorizations, whose cost profile is not per-tensor) are routed through
the legacy whole-tree path and cached as a single whole-model entry.

Sparse contributions
--------------------
A contribution may cover only a subset of the model's leaves (its
`leaf_paths` coverage descriptor, from `CRDTMergeState`). The planner
then keys each leaf task on that leaf's *per-leaf ordered contribution
subset*: a leaf untouched by a new sparse contribution derives the
same sub-root as before and stays a warm cache hit, so re-resolve cost
is O(changed leaves). A leaf covered by NO contribution inherits the
base leaf verbatim (absent-leaf semantics: inherit-base — the choice
is folded into `spec.cache_fragment()` so cache keys can never alias a
different semantics). Whole-model strategies densify sparse payloads
with base fill before the whole-tree path.

Strategies that declare a `LeafFold` (`Strategy.incremental`)
additionally support **prefix-fold resumption**: when a leaf's ordered
subset grew append-only, the executor probes the cache for the longest
previously-cached prefix, restores its float32 accumulator, and folds
only the new contributions — bit-equal to the full recompute by the
LeafFold contract (the fold IS the canonical math; see
strategies/base.py).

Sub-root derivation
-------------------
For leaf index i of a k-way merge described by a `repro.api.MergeSpec`:

    sub_root_i = SHA-256( domain || spec_fragment ||
                          base_i || k || d_1,i || ... || d_k,i ||
                          [seed || i  iff the strategy consumes a key] )

where `spec_fragment = spec.cache_fragment(with_reduction)` is the
spec's canonical hash over strategy + normalized cfg (+ reduction only
when it affects the output: binary-only strategies at k > 2), d_j,i is
`tensor_digest` of contribution j's leaf i in canonical (whole-model
content hash) order, and base_i the base leaf's digest (a fixed marker
when base is None, i.e. zeros). Because the fragment comes from the
spec's canonical encoding — cfg sorted, schema defaults filled in —
every entry point that means the same resolve derives the same keys:
`MergeSpec.digest()` is, transitively, the cache key. The seed and
leaf index enter only for key-consuming strategies: a deterministic
strategy's leaf output is independent of both, so its cache entries
survive arbitrary changes elsewhere in the model — the delta-efficiency
this engine exists for.

Caches are per-`EngineCache` instance: each `repro.api.Replica` owns
one, ending the cross-replica aliasing of the old process-global LRU.
The module-level cache functions (`set_cache_limit`, `cache_info`,
`clear_cache`, …) remain for compatibility and operate on a shared
default cache — prefer the per-replica methods in new code.

>>> import jax.numpy as jnp
>>> contribs = [{"w": jnp.ones((2, 2))}, {"w": jnp.zeros((2, 2))}]
>>> plan = plan_for(contribs, "weight_average")
>>> len(plan.tasks), plan.k
(1, 2)
>>> float(execute_plan(plan, contribs, use_cache=False)["w"][0, 0])
0.5
"""
from __future__ import annotations

import hashlib
import math
from collections import OrderedDict
from dataclasses import dataclass
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp

from repro.api.spec import coerce_spec, MergeSpec
from repro.core.compression import (
    compressed_tree_to_structure, CompressedLeaf, CompressedTree)
from repro.core.hashing import pytree_digest, tensor_digest, tree_nbytes
from repro.obs import CounterView, MetricsRegistry, span
from repro.strategies import get_strategy
from repro.strategies.base import run_fold, Strategy
from repro.strategies.select import recording as record_quantile_paths

_DOMAIN_LEAF = b"repro/engine/leaf-subroot/v2"
_DOMAIN_MODEL = b"repro/engine/model-subroot/v2"
_NO_BASE = b"\x00" * 32          # base=None marker (zeros_like base)


def _is_qleaf(x: Any) -> bool:
    return isinstance(x, CompressedLeaf)


def _dense_leaf(x: Any, *, obs: Optional[MetricsRegistry]) -> Any:
    """Densify one payload slice if (and only if) it arrived quantized.

    The op sequence is `compression.decompress_tree`'s exactly, so a
    merge of quantized contributions is byte-identical to
    densify-then-merge. Counted under
    `engine_events_total{event=dequant_leaves}`."""
    if not _is_qleaf(x):
        return x
    if obs is not None:
        obs.counter("engine_events_total").inc(event="dequant_leaves")
    import numpy as np
    a = (x.q.astype(np.float32) * x.scale).reshape(x.shape)
    return jnp.asarray(a, x.dtype)


def _as_spec(spec: Optional[MergeSpec], strategy_name: Optional[str],
             reduction: Optional[str], cfg: Dict[str, Any]) -> MergeSpec:
    """Normalize the two calling conventions: an explicit MergeSpec, or
    the legacy (strategy_name, reduction, **cfg) triple — the latter is
    wrapped in a lenient spec (the kwargs were never validated here and
    rejecting them now would break the shimmed entry points). A stray
    reduction=/cfg argument NEXT TO a spec raises instead of being
    silently ignored."""
    if spec is None and strategy_name is None:
        raise TypeError("either a MergeSpec or a strategy name is "
                        "required")
    if spec is not None and strategy_name is not None \
            and strategy_name != spec.strategy:
        raise TypeError(f"conflicting strategies: positional "
                        f"{strategy_name!r} vs spec {spec.strategy!r}")
    return coerce_spec(spec if spec is not None else strategy_name,
                       cfg, reduction=reduction, lenient=True)


# ---------------------------------------------------------------------------
# Per-contribution leaf metadata (digest memo)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ContribMeta:
    """Shape of one contribution as the planner sees it: tree structure
    plus per-leaf content digests. Content-addressed — under paper
    Assumption 11 an element id fully determines the payload bytes, so
    metas memoized by eid stay valid forever (and let the planner run
    against contributions whose payloads are not locally resident)."""
    treedef: Any                  # None for manifest-derived metas
    digests: Tuple[bytes, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    # keystr path per leaf, parallel to digests (flatten order). Lets
    # the planner map a sparse contribution's leaves onto the model's
    # leaves by path rather than by position.
    paths: Tuple[str, ...] = ()
    # per-leaf int8 dequantization scale for quantized contributions,
    # parallel to digests; None = dense fp payload. Digests always
    # describe the DEQUANTIZED tensor — content identity is defined on
    # wire-format values (compression.py), so a quantized and a
    # densified copy of the same contribution share cache keys.
    scales: Optional[Tuple[Optional[float], ...]] = None

    @property
    def leaf_count(self) -> int:
        return len(self.digests)


_META_MEMO: "OrderedDict[str, ContribMeta]" = OrderedDict()
_META_MEMO_LIMIT = 1024


def _flat_leaves(contribution: Any):
    """Flatten a contribution as the planner sees it: a `CompressedTree`
    by its structure, each `CompressedLeaf` kept whole."""
    if isinstance(contribution, CompressedTree):
        contribution = compressed_tree_to_structure(contribution)
    return jax.tree_util.tree_flatten_with_path(contribution,
                                                is_leaf=_is_qleaf)


def _build_meta(flat, treedef, digests: Sequence[bytes]) -> ContribMeta:
    leaves = [l for _, l in flat]
    quantized = any(_is_qleaf(l) for l in leaves)
    return ContribMeta(
        treedef=treedef,
        digests=tuple(digests),
        shapes=tuple(tuple(l.shape) if _is_qleaf(l) else tuple(jnp.shape(l))
                     for l in leaves),
        dtypes=tuple(jnp.dtype(l.dtype) if _is_qleaf(l)
                     else jnp.asarray(l).dtype for l in leaves),
        paths=tuple(jax.tree_util.keystr(p) for p, _ in flat),
        scales=tuple(float(l.scale) if _is_qleaf(l) else None
                     for l in leaves) if quantized else None,
    )


def _memoize(eid: str, meta: ContribMeta) -> ContribMeta:
    _META_MEMO[eid] = meta
    while len(_META_MEMO) > _META_MEMO_LIMIT:
        _META_MEMO.popitem(last=False)
    return meta


def contrib_meta(contribution: Any, *, eid: Optional[str] = None,
                 obs: Optional[MetricsRegistry] = None) -> ContribMeta:
    """Flatten + digest one contribution; memoized by content id.

    Quantized contributions (`CompressedTree`) are planned in place:
    leaves flatten to `CompressedLeaf` payloads, digests are computed
    on a transient per-leaf dequantization (one leaf live at a time —
    never the k x P densified copy), and the meta records the per-leaf
    scales.

    A miss (not a memo hit) is spanned as `engine.contrib_meta`, its
    hashed bytes counted under `hash_bytes_total{site=contrib_meta}`
    and the miss under `contrib_meta_total{source=hashed}` on `obs`,
    the registry of the replica that asked. A contribution whose id
    `Replica.contribute` computed from its content is memoized there
    (`seed_meta`), so its first resolve is a hit."""
    if eid is not None and eid in _META_MEMO:
        _META_MEMO.move_to_end(eid)
        return _META_MEMO[eid]
    flat, treedef = _flat_leaves(contribution)
    digests: List[bytes] = []
    nbytes = 0
    with span("engine.contrib_meta", eid=eid[:16] if eid else None) as sp:
        for _, l in flat:
            dense = _dense_leaf(l, obs=None)
            digests.append(tensor_digest(dense))
            nbytes += tree_nbytes(dense)
        sp.set(bytes=nbytes)
    if obs is not None:
        obs.counter("hash_bytes_total").inc(nbytes, site="contrib_meta")
        obs.counter("contrib_meta_total").inc(source="hashed")
    meta = _build_meta(flat, treedef, digests)
    return _memoize(eid, meta) if eid is not None else meta


def seed_meta(contribution: Any, eid: str, leaf_digests: Dict[str, bytes],
              *, obs: Optional[MetricsRegistry] = None
              ) -> Optional[ContribMeta]:
    """Memoize the planner's meta of a contribution from the per-leaf
    digests its content id `eid` was combined from
    (`hashing.pytree_leaf_digests`), hashing nothing: treedef, shapes,
    dtypes and paths come from the pytree, each digest from the map by
    its leaf's path (the map is in sorted path order, the meta in
    flatten order). Counted under `contrib_meta_total{source=
    content_id}` on `obs`.

    Where the digests cannot be shown to be the ones `contrib_meta`
    would hash, nothing is memoized and None returned: a quantized
    payload (a `CompressedTree` or any `CompressedLeaf`, whose planner
    digests describe dequantized values), or a map that does not hold
    exactly one digest per leaf path. A memo hit is returned as is."""
    if eid in _META_MEMO:
        _META_MEMO.move_to_end(eid)
        return _META_MEMO[eid]
    flat, treedef = _flat_leaves(contribution)
    paths = [jax.tree_util.keystr(p) for p, _ in flat]
    if any(_is_qleaf(l) for _, l in flat) \
            or len(leaf_digests) != len(paths) \
            or not all(p in leaf_digests for p in paths):
        return None
    if obs is not None:
        obs.counter("contrib_meta_total").inc(source="content_id")
    return _memoize(eid, _build_meta(
        flat, treedef, [leaf_digests[p] for p in paths]))


def note_meta(eid: str, paths: Sequence[str], digests: Sequence[bytes],
              shapes: Sequence[Tuple[int, ...]],
              dtypes: Sequence[Any],
              scales: Optional[Sequence[Optional[float]]] = None
              ) -> ContribMeta:
    """Memoize planner metadata announced over the wire (SparseManifest
    leaf refs) WITHOUT the payload being resident: the planner can then
    key per-leaf subsets — and fully-cached or fold-resumable plans can
    execute — before (or without) fetching a single chunk. treedef stays
    None: such metas are mapped onto the model by path.

    `scales` records the int8 dequantization scale announced per leaf
    ref (zero-point is identically 0 — the wire codec is symmetric):
    such a payload arrives as a `CompressedLeaf`, which the executor
    densifies slice by slice."""
    meta = ContribMeta(
        treedef=None,
        digests=tuple(digests),
        shapes=tuple(tuple(s) for s in shapes),
        dtypes=tuple(jnp.dtype(d) for d in dtypes),
        paths=tuple(paths),
        scales=(tuple(None if s is None else float(s) for s in scales)
                if scales is not None and any(s is not None for s in scales)
                else None),
    )
    return _memoize(eid, meta)


def memoized_meta(eid: str) -> Optional[ContribMeta]:
    """Planner metadata for a content id seen before, else None. Lets
    resolve() plan (and fully-cached plans complete) without fetching
    the payload at all."""
    meta = _META_MEMO.get(eid)
    if meta is not None:
        _META_MEMO.move_to_end(eid)
    return meta


def clear_meta_memo() -> None:
    _META_MEMO.clear()


# ---------------------------------------------------------------------------
# Plans
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LeafTask:
    index: int                    # global flatten index (key derivation)
    path: str                     # keystr; maps sparse payloads to leaves
    sub_root: bytes               # per-tensor content address of output
    shape: Tuple[int, ...]
    dtype: Any
    stacked_nbytes: int           # k_i * leaf nbytes: live bytes to execute
    # this leaf's ordered contribution subset: positions into the plan's
    # canonical contribution list, and their leaf digests (canonical
    # order preserved). Dense plans cover every position at every leaf.
    contributors: Tuple[int, ...] = ()
    digests: Tuple[bytes, ...] = ()
    base_frag: bytes = b""

    @property
    def k(self) -> int:
        return len(self.contributors)


@dataclass(frozen=True)
class MergePlan:
    strategy: str
    reduction: str
    seed: int
    k: int
    cfg: Tuple[Tuple[str, Any], ...]      # sorted (name, value) pairs
    treedef: Any
    tasks: Tuple[LeafTask, ...]
    spec: Optional[MergeSpec] = None      # the spec this plan realizes
    frag: bytes = b""                     # spec fragment (prefix probing)
    # per-contribution coverage (None entry = dense); None = all dense
    coverages: Optional[Tuple[Optional[Tuple[str, ...]], ...]] = None
    # model leaf indices covered by NO contribution: inherit-base
    base_only: Tuple[int, ...] = ()

    def cfg_dict(self) -> Dict[str, Any]:
        return dict(self.cfg)


def _leaf_subroot(frag: bytes, base_frag: bytes,
                  digests: Sequence[bytes], needs_key: bool,
                  seed: int, index: int) -> bytes:
    """Sub-root over ONE leaf's ordered contribution subset. Dense plans
    pass every contribution's digest, reproducing the PR-4 derivation
    byte-for-byte; sparse plans pass only the covering subset — so a
    sparse leaf's key equals the key of a dense merge over exactly that
    subset, which is the per-leaf semantics (and what makes warm entries
    shareable between the two)."""
    h = hashlib.sha256(_DOMAIN_LEAF)
    h.update(frag)
    h.update(base_frag)
    h.update(len(digests).to_bytes(4, "big"))
    for d in digests:
        h.update(d)
    if needs_key:
        # key-consuming strategies: output depends on the Merkle-
        # derived seed and the global leaf index (leafwise fold_in)
        h.update(str(seed).encode())
        h.update(index.to_bytes(4, "big"))
    return h.digest()


def plan_merge(metas: Sequence[ContribMeta],
               strategy_name: Optional[str] = None, *,
               base: Any = None, seed: int = 0,
               reduction: Optional[str] = None,
               spec: Optional[MergeSpec] = None,
               coverages: Optional[Sequence[Optional[Tuple[str, ...]]]]
               = None, obs: Optional[MetricsRegistry] = None,
               **cfg) -> MergePlan:
    """Emit a per-leaf merge plan from contribution metadata (canonical
    order). Payloads are not needed to plan — only their digests. Takes
    either a MergeSpec (`spec=`) or the legacy strategy-name + kwargs
    form (wrapped in a lenient spec).

    With a base, its leaves are digested here (span
    `engine.base_digest`), and the bytes counted under
    `hash_bytes_total{site=base}` on `obs`, the registry of the replica
    that asked.

    `coverages` (parallel to `metas`) marks sparse contributions: a
    tuple of keystr leaf paths the contribution carries, or None for
    dense. Each leaf task is keyed on the subset of contributions
    covering that leaf; a leaf covered by none inherits the base leaf
    (requires base=). The model structure comes from the first dense
    contribution, falling back to the base when every contribution is
    sparse."""
    if not metas:
        raise ValueError("plan_merge() requires at least one contribution")
    spec = _as_spec(spec, strategy_name, reduction, cfg)
    strat = get_strategy(spec.strategy)
    if strat.whole_model or strat.leaf_fn is None:
        raise ValueError(
            f"strategy {spec.strategy!r} is whole-model; use merge()")
    k = len(metas)
    if coverages is None:
        coverages = (None,) * k
    if len(coverages) != k:
        raise ValueError("coverages must parallel metas")
    # dense metas carrying their own treedef define the model structure
    dense = [j for j, cov in enumerate(coverages)
             if cov is None and metas[j].treedef is not None]
    with span("engine.plan", strategy=spec.strategy, k=k,
              leaves=(metas[dense[0]].leaf_count if dense else 0)):
        frag = spec.cache_fragment(
            with_reduction=(strat.binary_only and k > 2))
        if dense:
            first = metas[dense[0]]
            for j in dense[1:]:
                m = metas[j]
                if m.treedef != first.treedef or m.shapes != first.shapes \
                        or m.dtypes != first.dtypes:
                    raise ValueError(
                        "contributions disagree on tree structure")
            treedef = first.treedef
            paths = _leaf_paths(treedef)
            shapes, dtypes = first.shapes, first.dtypes
        else:
            if base is None:
                raise ValueError(
                    "every contribution is sparse and no base was given; "
                    "the model structure must come from a dense "
                    "contribution or the base model")
            bflat, treedef = jax.tree_util.tree_flatten(base)
            paths = _leaf_paths(treedef)
            shapes = tuple(tuple(jnp.shape(l)) for l in bflat)
            dtypes = tuple(jnp.asarray(l).dtype for l in bflat)
        n_leaves = len(paths)
        path_index = {p: i for i, p in enumerate(paths)}
        contributors: List[List[int]] = [[] for _ in range(n_leaves)]
        leaf_digests: List[List[bytes]] = [[] for _ in range(n_leaves)]
        for j, (m, cov) in enumerate(zip(metas, coverages)):
            if cov is None and m.treedef is not None:
                for i in range(n_leaves):
                    contributors[i].append(j)
                    leaf_digests[i].append(m.digests[i])
                continue
            # path-mapped: sparse, or dense-by-manifest (treedef unknown)
            if cov is not None and set(m.paths) != set(cov):
                raise ValueError(
                    f"contribution {j}: coverage descriptor does not "
                    "match its leaf paths")
            for local, p in enumerate(m.paths):
                i = path_index.get(p)
                if i is None:
                    raise ValueError(
                        f"contribution {j} covers leaf {p!r} which the "
                        "model structure does not have")
                if m.shapes[local] != shapes[i] \
                        or jnp.dtype(m.dtypes[local]) != jnp.dtype(dtypes[i]):
                    raise ValueError(
                        f"contribution {j}: leaf {p!r} shape/dtype "
                        "disagrees with the model structure")
                contributors[i].append(j)
                leaf_digests[i].append(m.digests[local])
        if base is None:
            base_frags: Sequence[bytes] = [_NO_BASE] * n_leaves
        else:
            base_leaves = treedef.flatten_up_to(base)
            nbytes = tree_nbytes(base_leaves)
            with span("engine.base_digest", bytes=nbytes):
                base_frags = [tensor_digest(bl) for bl in base_leaves]
            if obs is not None:
                obs.counter("hash_bytes_total").inc(nbytes, site="base")
        tasks: List[LeafTask] = []
        base_only: List[int] = []
        for i in range(n_leaves):
            ki = len(contributors[i])
            if ki == 0:
                # absent-leaf semantics: inherit-base (Remark 16 ref.
                # semantics — the spec fragment encodes this choice)
                if base is None:
                    raise ValueError(
                        f"leaf {paths[i]!r} is covered by no contribution "
                        "and no base model was given (absent leaves "
                        "inherit the base)")
                base_only.append(i)
                continue
            digs = tuple(leaf_digests[i])
            # every slice is stacked dense, a quantized one included
            # (`_dense_leaf`): k_i slices at the leaf's own width
            stacked = ki * math.prod(shapes[i]) * \
                jnp.dtype(dtypes[i]).itemsize
            tasks.append(
                LeafTask(index=i, path=paths[i],
                         sub_root=_leaf_subroot(frag, base_frags[i], digs,
                                                strat.needs_key, seed, i),
                         shape=shapes[i], dtype=dtypes[i],
                         stacked_nbytes=stacked,
                         contributors=tuple(contributors[i]),
                         digests=digs, base_frag=base_frags[i]))
    any_sparse = any(c is not None for c in coverages)
    return MergePlan(strategy=spec.strategy, reduction=spec.reduction,
                     seed=seed, k=k, cfg=spec.cfg,
                     treedef=treedef, tasks=tuple(tasks), spec=spec,
                     frag=frag,
                     coverages=tuple(coverages) if any_sparse else None,
                     base_only=tuple(base_only))


def plan_for(contribs: Sequence[Any],
             strategy_name: Optional[str] = None, *,
             contrib_ids: Optional[Sequence[str]] = None,
             base: Any = None, seed: int = 0,
             reduction: Optional[str] = None,
             spec: Optional[MergeSpec] = None,
             coverages: Optional[Sequence[Optional[Tuple[str, ...]]]]
             = None, obs: Optional[MetricsRegistry] = None,
             **cfg) -> MergePlan:
    """Convenience planner over resident payloads (ids memoize digests)."""
    ids: Sequence[Optional[str]] = contrib_ids or [None] * len(contribs)
    metas = [contrib_meta(c, eid=e, obs=obs) for c, e in zip(contribs, ids)]
    return plan_merge(metas, strategy_name, base=base, seed=seed,
                      reduction=reduction, spec=spec,
                      coverages=coverages, obs=obs, **cfg)


def _leaf_paths(treedef) -> List[str]:
    """keystr path per leaf, in flatten order."""
    dummy = jax.tree_util.tree_unflatten(
        treedef, list(range(treedef.num_leaves)))
    flat = jax.tree_util.tree_flatten_with_path(dummy)[0]
    paths = [""] * treedef.num_leaves
    for path, idx in flat:
        paths[idx] = jax.tree_util.keystr(path)
    return paths


# ---------------------------------------------------------------------------
# Byte-budgeted sub-root cache (per-leaf entries + whole-model entries)
# ---------------------------------------------------------------------------

_DEFAULT_ENTRY_LIMIT = 65536
_DEFAULT_BYTE_LIMIT = 256 * 2 ** 20


class CacheInfo(NamedTuple):
    entries: int
    bytes: int
    entry_limit: int
    byte_limit: int
    hits: int
    misses: int


class EngineCache:
    """One replica's merge-output cache + executor counters.

    sub_root -> (value, nbytes). Values are merged leaf arrays
    (LeafTask entries) or whole output pytrees (whole-model
    strategies). Eviction is LRU under BOTH an entry count and a
    resident-byte budget: merge outputs are model tensors, so counting
    entries alone under-controls memory by orders of magnitude between
    a layernorm and an embedding.

    Instances are independent — each `repro.api.Replica` owns one, so
    two replicas in a process no longer alias each other's LRU order,
    byte budget, or hit/miss counters. The module-level functions below
    keep operating on one shared `default_cache()` for compatibility.

    Counters live on a per-cache `repro.obs` registry (`self.obs`,
    injectable for Replica-scoped telemetry); `self.stats` remains a
    Counter-shaped read-through view over the
    `engine_events_total{event=...}` series, so existing call sites and
    tests are unchanged.
    """

    __slots__ = ("_data", "_bytes", "entry_limit", "byte_limit", "obs",
                 "stats", "peak_stacked")

    def __init__(self, entries: int = _DEFAULT_ENTRY_LIMIT, *,
                 bytes: int = _DEFAULT_BYTE_LIMIT,  # noqa: A002
                 obs: Optional[MetricsRegistry] = None):
        # key -> (value, nbytes, aux); aux is an incremental strategy's
        # float32 fold accumulator (None otherwise), counted in nbytes
        self._data: "OrderedDict[bytes, Tuple[Any, int, Any]]" = \
            OrderedDict()
        self._bytes = 0
        self.entry_limit = entries
        self.byte_limit = bytes
        self.obs = obs if obs is not None else MetricsRegistry()
        self.stats = CounterView(self.obs, "engine_events_total")
        self.peak_stacked = 0         # executor high-water mark

    # -------------------------------------------------------------- limits

    def set_limit(self, entries: Optional[int] = None, *,
                  bytes: Optional[int] = None) -> None:  # noqa: A002
        """Bound the cache; evicts LRU-first immediately. `entries`
        caps cached tensors; `bytes` caps resident payload bytes
        (size-aware eviction). Omitted arguments stay unchanged."""
        if entries is not None:
            if entries < 1:
                raise ValueError("cache entry limit must be >= 1")
            self.entry_limit = entries
        if bytes is not None:
            if bytes < 0:
                raise ValueError("cache byte limit must be >= 0")
            self.byte_limit = bytes
        self._evict()

    def info(self) -> CacheInfo:
        return CacheInfo(len(self._data), self._bytes, self.entry_limit,
                         self.byte_limit, self.stats["hits"],
                         self.stats["misses"])

    def clear(self) -> None:
        self._data.clear()
        self._bytes = 0
        self.obs.gauge("engine_cache_resident_bytes").set(0)

    # ------------------------------------------------------------- entries

    def _evict(self) -> None:
        evicted = 0
        while self._data and (len(self._data) > self.entry_limit
                              or self._bytes > self.byte_limit):
            _, (_, nbytes, _) = self._data.popitem(last=False)
            self._bytes -= nbytes
            evicted += 1
        if evicted:
            self.stats["evictions"] += evicted
            self.obs.gauge("engine_cache_resident_bytes").set(self._bytes)

    def get(self, key: bytes) -> Optional[Any]:
        if key in self._data:
            self._data.move_to_end(key)
            return self._data[key][0]
        return None

    def put(self, key: bytes, value: Any, nbytes: int,
            aux: Any = None) -> None:
        if key in self._data:
            self._bytes -= self._data[key][1]
        self._data[key] = (value, nbytes, aux)
        self._data.move_to_end(key)
        self._bytes += nbytes
        self.obs.gauge("engine_cache_resident_bytes").set(self._bytes)
        self._evict()

    def aux(self, key: bytes) -> Optional[Any]:
        """The fold accumulator cached alongside a value (no recency
        bump, no hit/miss counting — this is a resumption probe)."""
        ent = self._data.get(key)
        return ent[2] if ent is not None else None

    def __contains__(self, key: bytes) -> bool:
        return key in self._data

    def lookup(self, key: bytes) -> Optional[Any]:
        """Fetch-free probe: the cached value (counting a hit) or None
        (counting nothing — the caller goes on to compute through a
        path that records the miss itself)."""
        val = self.get(key)
        if val is not None:
            self.stats["hits"] += 1
        return val

    def split(self, plan: "MergePlan") -> Tuple[List["LeafTask"],
                                                List["LeafTask"]]:
        """(hits, misses) — membership only, no recency/counters."""
        hits = [t for t in plan.tasks if t.sub_root in self._data]
        misses = [t for t in plan.tasks if t.sub_root not in self._data]
        return hits, misses

    # ------------------------------------------------------------ counters

    def exec_stats(self) -> Dict[str, int]:
        """Executor counters since the last reset: `leaf_tasks`
        executed, `dispatches` issued, `batched_leaves` fused into
        multi-leaf dispatches, cache `hits`/`misses`, and
        `peak_stacked_bytes` — the largest set of stacked contribution
        slices ever live at once."""
        out = dict(self.stats)
        out["peak_stacked_bytes"] = self.peak_stacked
        return out

    def reset_exec_stats(self) -> None:
        self.stats.clear()
        self.peak_stacked = 0
        self.obs.gauge("engine_peak_stacked_bytes").set(0)
        self.obs.gauge("engine_working_set_bytes").set(0)

    def note_stacked(self, nbytes: int) -> None:
        self.peak_stacked = max(self.peak_stacked, nbytes)
        self.obs.gauge("engine_peak_stacked_bytes").set_max(nbytes)


_DEFAULT_CACHE = EngineCache()


def default_cache() -> EngineCache:
    """The process-wide cache the module-level helpers (and every call
    that does not pass `cache=`) operate on."""
    return _DEFAULT_CACHE


def _cache_or_default(cache: Optional[EngineCache]) -> EngineCache:
    return cache if cache is not None else _DEFAULT_CACHE


# Module-level cache helpers. DEPRECATION NOTE: these act on the shared
# default cache only and predate per-replica isolation — new code
# should hold an EngineCache (usually via repro.api.Replica, whose
# set_cache_limit/cache_info methods scope to that replica) and pass it
# as `cache=`. Kept working, without warnings, because they remain the
# right knobs for single-replica processes and the test/bench harness.


def set_cache_limit(entries: Optional[int] = None, *,
                    bytes: Optional[int] = None) -> None:  # noqa: A002
    """Bound the DEFAULT merge-output cache (see EngineCache.set_limit;
    per-replica caches are bounded via Replica.set_cache_limit)."""
    _DEFAULT_CACHE.set_limit(entries, bytes=bytes)


def cache_info() -> CacheInfo:
    """Occupancy/limits/counters of the DEFAULT cache.

    >>> _ = set_cache_limit(entries=8, bytes=1 << 20)
    >>> cache_info().entry_limit, cache_info().byte_limit
    (8, 1048576)
    >>> reset_cache_limits()
    """
    return _DEFAULT_CACHE.info()


def reset_cache_limits() -> None:
    """Restore the default cache's entry/byte limits (tests, doctests)."""
    _DEFAULT_CACHE.set_limit(_DEFAULT_ENTRY_LIMIT,
                             bytes=_DEFAULT_BYTE_LIMIT)


def clear_cache() -> None:
    """Drop the default cache's merge outputs AND the (process-wide)
    planner digest memos."""
    _DEFAULT_CACHE.clear()
    _META_MEMO.clear()


def cached(key: bytes, cache: Optional[EngineCache] = None) -> bool:
    return key in _cache_or_default(cache)


def cache_lookup(key: bytes,
                 cache: Optional[EngineCache] = None) -> Optional[Any]:
    return _cache_or_default(cache).lookup(key)


def plan_cached_split(plan: "MergePlan",
                      cache: Optional[EngineCache] = None
                      ) -> Tuple[List["LeafTask"], List["LeafTask"]]:
    return _cache_or_default(cache).split(plan)


def exec_stats(cache: Optional[EngineCache] = None) -> Dict[str, int]:
    return _cache_or_default(cache).exec_stats()


def reset_exec_stats(cache: Optional[EngineCache] = None) -> None:
    _cache_or_default(cache).reset_exec_stats()


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


def execute_plan(plan: MergePlan, contribs: Optional[Sequence[Any]], *,
                 base: Any = None, use_cache: bool = True,
                 max_batch_bytes: Optional[int] = None,
                 cache: Optional[EngineCache] = None) -> Any:
    """Run a merge plan and return the merged pytree.

    `contribs` is the canonical-order payload list; it may be None when
    every task is already cached (the zero-fetch re-resolve path).
    Live stacked memory is bounded: the executor materialises one
    leaf's [k, ...] slice stack (or one fused batch — whose per-leaf
    pieces plus concatenated rows are both transiently live, so the
    batch byte cap `max_batch_bytes` defaults to the largest single
    leaf's stack at the batch's own k, keeping the batched peak within
    ~2 leaves' worth, and a batch's leaves the same as another leaf's
    k grows) at a time — never the k full model copies the legacy path
    stacks. The executor waits for each dispatch before it builds the
    next (and a chain too large for what is free runs in waited-for
    blocks: `_chain_blocks`): on an asynchronous device (a TPU) the host
    would otherwise enqueue many dispatches ahead, and all their
    transients would be live at once.
    """
    cache = _cache_or_default(cache)
    strat = get_strategy(plan.strategy)
    n_out = len(plan.tasks) + len(plan.base_only)
    outputs: List[Optional[Any]] = [None] * n_out
    cache.obs.gauge("engine_plan_leaves").set(len(plan.tasks))
    cache.obs.gauge("engine_sparse_leaves_skipped").set(
        sum(1 for t in plan.tasks if t.k < plan.k) + len(plan.base_only))
    base_leaves = (plan.treedef.flatten_up_to(base)
                   if base is not None else None)
    if plan.base_only and base_leaves is None:
        raise ValueError("plan has inherit-base leaves but no base was "
                         "supplied to execute_plan()")
    for i in plan.base_only:
        outputs[i] = base_leaves[i]          # inherit-base

    misses: List[LeafTask] = []
    resumes: List[Tuple[LeafTask, int, Any]] = []
    for t in plan.tasks:
        hit = cache.get(t.sub_root) if use_cache else None
        if hit is not None:
            outputs[t.index] = hit
            cache.stats["hits"] += 1
        else:
            if use_cache:
                cache.stats["misses"] += 1
                rp = _fold_resume_point(strat, plan, t, cache)
                if rp is not None:
                    resumes.append((t, rp[0], rp[1]))
                    continue
            misses.append(t)
    with span("engine.execute", strategy=plan.strategy, k=plan.k,
              leaves=len(plan.tasks),
              misses=len(misses) + len(resumes)):
        if misses or resumes:
            if contribs is None:
                raise KeyError(
                    f"{len(misses) + len(resumes)} leaf tasks miss the "
                    "cache but no payloads were supplied; fetch the "
                    "contribution blobs first")
            if len(contribs) != plan.k:
                raise ValueError(f"plan expects {plan.k} contributions, "
                                 f"got {len(contribs)}")
            flat = _flatten_contribs(plan, contribs)

            def leaf_of(j: int, t: LeafTask):
                # a quantized slice is densified on access, one at a
                # time (exact decompress_tree math, counted)
                f = flat[j]
                if f is None:
                    raise KeyError(
                        f"contribution {j} is needed by leaf {t.path!r} "
                        "but its payload was not supplied")
                return _dense_leaf(
                    f[t.index] if isinstance(f, list) else f[t.path],
                    obs=cache.obs)

            cfg = plan.cfg_dict()
            for t, m, aux in resumes:
                # prefix-fold resumption: the leaf's ordered subset grew
                # append-only past a cached prefix — restore that
                # prefix's accumulator and fold only the new tail
                new = [leaf_of(j, t) for j in t.contributors[m:]]
                b = _base_leaf(base_leaves, t.index, new[0])
                cache.note_stacked(t.stacked_nbytes)
                kw = dict(strat.defaults)
                kw.update(cfg)
                folded = run_fold(strat.fold, new, b, acc=aux, k=t.k, **kw)
                with span("engine.wait", leaves=1):
                    val, acc = jax.block_until_ready(folded)
                outputs[t.index] = val
                cache.stats["leaf_tasks"] += 1
                cache.stats["dispatches"] += 1
                cache.stats["fold_resumes"] += 1
                cache.obs.counter("resolve_fold_updates_total").inc(
                    t.k - m)
                cache.put(t.sub_root, val,
                          int(val.nbytes) + int(acc.nbytes), aux=acc)
            if misses:
                leaf_bytes = max(t.stacked_nbytes // t.k
                                 for t in plan.tasks)
                for group in _dispatch_groups(strat, misses,
                                              max_batch_bytes, leaf_bytes):
                    if len(group) == 1:
                        with record_quantile_paths() as taken:
                            o, a = _execute_leaf(strat, plan, group[0],
                                                 leaf_of, base_leaves,
                                                 cache)
                        for path in dict.fromkeys(taken):
                            cache.obs.counter(
                                "exact_quantile_leaves_total").inc(
                                    path=path)
                        out, auxs = [o], [a]
                    else:
                        out, auxs = _execute_batch(
                            strat, plan, group, leaf_of, base_leaves,
                            cache)
                        cache.stats["batched_leaves"] += len(group)
                    with span("engine.wait", leaves=len(group)):
                        jax.block_until_ready(out)
                    cache.stats["dispatches"] += 1
                    cache.stats["leaf_tasks"] += len(group)
                    for t, o, a in zip(group, out, auxs):
                        outputs[t.index] = o
                        if use_cache:
                            nb = int(o.nbytes) + (int(a.nbytes)
                                                  if a is not None else 0)
                            cache.put(t.sub_root, o, nb, aux=a)
    return jax.tree_util.tree_unflatten(plan.treedef, outputs)


def _flatten_contribs(plan: MergePlan, contribs: Sequence[Any]
                      ) -> List[Any]:
    """Per-contribution leaf accessors: a flatten-order list for dense
    contributions, a path-keyed dict for sparse ones, None for payloads
    the executor was told it will not need. Quantized contributions
    (`CompressedTree`) flatten to their `CompressedLeaf` payloads:
    each is densified where it is read, so only the slices of the leaf
    or batch in hand are ever dense, never the whole contribution."""
    covs = plan.coverages or (None,) * plan.k
    out: List[Any] = []
    for c, cov in zip(contribs, covs):
        if isinstance(c, CompressedTree):
            c = compressed_tree_to_structure(c)
        if c is None:
            out.append(None)
        elif cov is None:
            out.append(plan.treedef.flatten_up_to(c))
        else:
            pairs = jax.tree_util.tree_flatten_with_path(
                c, is_leaf=_is_qleaf)[0]
            out.append({jax.tree_util.keystr(p): l for p, l in pairs})
    return out


def _fold_resume_point(strat: Strategy, plan: MergePlan, task: LeafTask,
                       cache: "EngineCache"
                       ) -> Optional[Tuple[int, Any]]:
    """Longest cached proper prefix of a missed fold-capable task:
    (m, accumulator) where contributions [0, m) are already folded, or
    None. Probes longest-first — the append-only common case hits at
    m = k-1 immediately."""
    fold = strat.fold
    if fold is None or task.k < 2 or task.k < fold.min_k:
        return None
    for m in range(task.k - 1, fold.min_k - 1, -1):
        key = _leaf_subroot(plan.frag, task.base_frag,
                            task.digests[:m], strat.needs_key,
                            plan.seed, task.index)
        aux = cache.aux(key)
        if aux is not None:
            return m, aux
    return None


def plan_needed_ids(plan: MergePlan,
                    cache: Optional["EngineCache"] = None, *,
                    use_cache: bool = True) -> Tuple[int, ...]:
    """Contribution positions whose payloads execution will need under
    the current cache state: contributors of cache-missed tasks, minus
    the already-folded prefix of fold-resumable tasks. Lets resolve
    narrow its fetch to O(changed) payloads."""
    cache = _cache_or_default(cache)
    strat = get_strategy(plan.strategy)
    needed: set = set()
    for t in plan.tasks:
        if use_cache and t.sub_root in cache:
            continue
        rp = _fold_resume_point(strat, plan, t, cache) if use_cache \
            else None
        lo = rp[0] if rp is not None else 0
        needed.update(t.contributors[lo:])
    return tuple(sorted(needed))


def _dispatch_groups(strat: Strategy, misses: List[LeafTask],
                     max_batch_bytes: Optional[int], leaf_bytes: int
                     ) -> List[List[LeafTask]]:
    """Partition missed tasks into dispatches. Elementwise strategies
    fuse same-dtype leaves (flattened + concatenated) up to the batch
    byte cap (by default `leaf_bytes`, the plan's largest leaf, times
    the batch's k); everything else runs one leaf per dispatch. Under
    sparse contributions only leaves with the SAME ordered contributor
    subset fuse — a [k_i, N] batch has one k_i."""
    if not strat.batchable:
        return [[t] for t in misses]
    groups: List[List[LeafTask]] = []
    by_dtype: Dict[Any, List[LeafTask]] = {}
    for t in misses:
        by_dtype.setdefault((t.dtype, t.contributors), []).append(t)
    for (_, contributors), tasks in by_dtype.items():
        cap = max_batch_bytes if max_batch_bytes is not None \
            else leaf_bytes * len(contributors)
        # largest-first packing: the big leaves that fill a batch alone
        # go first, so the many small leaves behind them still fuse
        # instead of being fragmented by an oversized neighbour
        # (dispatch order is irrelevant to output bytes — tasks are
        # independent)
        tasks = sorted(tasks, key=lambda t: (-t.stacked_nbytes, t.index))
        cur: List[LeafTask] = []
        cur_bytes = 0
        for t in tasks:
            if cur and cur_bytes + t.stacked_nbytes > cap:
                groups.append(cur)
                cur, cur_bytes = [], 0
            cur.append(t)
            cur_bytes += t.stacked_nbytes
        if cur:
            groups.append(cur)
    return groups


def _base_leaf(base_leaves, idx: int, like) -> Any:
    if base_leaves is None:
        return jnp.zeros_like(like)
    return base_leaves[idx]


def _execute_leaf(strat: Strategy, plan: MergePlan, task: LeafTask,
                  leaf_of, base_leaves, cache: EngineCache
                  ) -> Tuple[Any, Any]:
    """One leaf over its ordered contributor subset: stack the k_i
    slices and apply the strategy's leaf function (folding per-leaf for
    binary-only strategies at k_i > 2, with the legacy per-step seeds).
    Returns (value, aux): aux is the float32 fold accumulator for
    incremental strategies (cached for resumption), else None."""
    i = task.index
    slices = [leaf_of(j, task) for j in task.contributors]
    ki = len(slices)
    cfg = plan.cfg_dict()
    cache.note_stacked(task.stacked_nbytes)
    if strat.binary_only and ki > 2:
        if plan.reduction == "tree":
            return _leaf_tree_fold(strat, slices, base_leaves, i,
                                   plan.seed, cfg), None
        return _leaf_seq_fold(strat, slices, base_leaves, i, plan.seed,
                              cfg), None
    b = _base_leaf(base_leaves, i, slices[0])
    kw = {**strat.defaults, **cfg}
    if strat.fold is not None and ki >= strat.fold.min_k:
        # drive the canonical fold directly (identical math to leaf_fn,
        # which is run_fold over the same inputs) to retain the
        # accumulator for later resumption; waited for step by step, it
        # holds buffers of one contribution's leaf, whatever k is
        planned = _chain_blocks(task.shape, task.stacked_nbytes // ki,
                                slices[0])
        return _in_blocks(
            cache, plan.strategy, task.path, 1, planned,
            lambda blocks: _fold_blocks(strat.fold, slices, b, blocks,
                                        **kw))
    blocked = strat.blocked(**kw) if strat.blocked is not None else None
    if blocked is not None:
        # blocks are picked before the stack is built: it counts in the
        # working set, not in what is free
        planned = _chain_blocks(task.shape, task.stacked_nbytes,
                                slices[0], held=1, first=_THRESHOLD_STACKS)
        return _in_blocks(
            cache, plan.strategy, task.path, 1, planned,
            lambda blocks: blocked(jnp.stack(slices), b, blocks)), None
    stacked = jnp.stack(slices)
    return strat.apply_leaf(stacked, b, leaf_index=i, seed=plan.seed,
                            **cfg), None


# Buffers of its stack's size ([k, ...] of its leaf or fused batch) that
# a chain of eager ops holds at once when the host dispatches it ahead
# of the device. Measured on the v5e: 16.0 for TIES on a whole
# [3, 4, 8, 2048, 1408] expert stack, 16.7 for a k=2 task-arithmetic
# fold over a fused [2, N] stack. A fold waited for step by step
# (`_fold_blocks`) is reckoned at as many buffers of one contribution's
# leaf: a step holds the accumulator, the contribution and the base in
# float32, their difference and the new accumulator, 10 buffers of a
# bfloat16 leaf.
_CHAIN_STACKS = 16
# TIES's threshold stage, run whole before its blocks: the stack, s - b,
# the magnitudes and the selection's counts
_THRESHOLD_STACKS = 4


def _in_blocks(cache: EngineCache, strategy: str, path: str, leaves: int,
               planned: Tuple[List[Tuple[slice, ...]], int], run):
    """`run(blocks)` in the blocks `_chain_blocks` planned, with their
    working set; a run in more than one block is spanned and counted."""
    blocks, working_set = planned
    cache.obs.gauge("engine_working_set_bytes").set_max(working_set)
    if len(blocks) == 1:
        return run(blocks)
    with span("engine.slices", path=path, leaves=leaves,
              slices=len(blocks), working_set_bytes=working_set):
        out = run(blocks)
    cache.obs.counter("sliced_leaves_total").inc(leaves, strategy=strategy)
    cache.obs.counter("leaf_slices_total").inc(len(blocks),
                                               strategy=strategy)
    return out


def _chain_blocks(shape: Tuple[int, ...], stack: int, like, *,
                  held: int = 0, first: int = 0
                  ) -> Tuple[List[Tuple[slice, ...]], int]:
    """The blocks of the leading axes of `shape` in which to run a
    chain over a stack of `stack` bytes (`[()]`: whole), and its
    working set reckoned in bytes: `held` stacks kept whole beside one
    block's chain of `_CHAIN_STACKS` stacks, or a stage of `first`
    stacks run whole before the blocks, whichever is larger.

    Whole where that fits half of what the device holding `like` has
    free (`bytes_limit` less `bytes_in_use`), or where the device
    reports neither (a CPU); else the fewest blocks, a power of two, up
    to `_CHAIN_STACKS` (beyond, one block's chain is below a stack),
    that fit it. The other half holds the outputs of the leaves still
    to run and what the allocator cannot place. A power of two, so that
    the block shapes, compiled once, change only where what is free
    halves or doubles.

    Only elementwise chains, bit for bit the same block by block, have
    blocks: the linear family's folds (`Strategy.fold`) and TIES's
    quantile trim (`Strategy.blocked`). DARE's per-leaf random stream
    and SLERP's whole-leaf norms would change bits, so those, as every
    other strategy, run whole-leaf."""
    n = math.prod(shape)

    def reckon(blocks):
        largest = max(math.prod(_block_shape(shape, blk))
                      for blk in blocks)
        return max(first * stack,
                   held * stack + _CHAIN_STACKS * stack * largest // n)

    blocks = [()]
    working_set = reckon(blocks)
    free = _free_bytes(like)
    parts = 1
    while free is not None and working_set > free // 2 \
            and parts < _CHAIN_STACKS:
        parts *= 2
        blocks = leading_blocks(shape, parts)
        working_set = reckon(blocks)
    return blocks, working_set


def _free_bytes(x) -> Optional[int]:
    """`bytes_limit` less `bytes_in_use` of the device holding `x`,
    where it reports them."""
    devices = getattr(x, "devices", None)
    if devices is None:
        return None
    stats = next(iter(devices())).memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


def _fold_blocks(fold, slices, b, blocks, **kw):
    """`run_fold` over the leaves `slices` (the contributions') and the
    base leaf `b`: one block of their leading axes at a time and, in a
    block, one contribution at a time, each step waited for before the
    next is dispatched, so that what the fold holds does not grow with
    k; the blocks' values and accumulators joined in row-major order.
    The steps are `run_fold`'s and the fold is elementwise, so the bits
    are the whole run's."""
    vals, accs = [], []
    for blk in blocks:
        part = (lambda x: x[blk]) if blk else (lambda x: x)
        bb, acc = part(b), None
        for x in slices[:-1]:
            acc = jax.block_until_ready(run_fold(
                fold, [part(x)], bb, acc=acc, finalize=False, **kw)[1])
        val, acc = jax.block_until_ready(run_fold(
            fold, [part(slices[-1])], bb, acc=acc, k=len(slices), **kw))
        vals.append(val)
        accs.append(acc)
    if len(blocks) == 1:
        return vals[0], accs[0]
    return (jnp.concatenate([v.reshape(-1) for v in vals]).reshape(b.shape),
            jnp.concatenate([a.reshape(-1) for a in accs]).reshape(b.shape))


def leading_blocks(shape: Tuple[int, ...], n: int
                   ) -> List[Tuple[slice, ...]]:
    """About `n` rectangular blocks of an array of `shape` along its
    leading axes, in row-major order (their flattened contents,
    concatenated, are the array's): contiguous ranges of axis 0 whose
    sizes differ by one at most; where axis 0 is shorter than `n`,
    each of its entries split along the next axis the same way."""
    d0 = shape[0]
    if n <= d0 or len(shape) == 1:
        n = max(1, min(n, d0))
        edges = [d0 * j // n for j in range(n + 1)]
        return [(slice(lo, hi),) for lo, hi in zip(edges, edges[1:])]
    inner = leading_blocks(shape[1:], -(-n // d0))
    return [(slice(j, j + 1),) + blk for j in range(d0) for blk in inner]


def _block_shape(shape: Tuple[int, ...], blk: Tuple[slice, ...]
                 ) -> Tuple[int, ...]:
    return tuple(s.stop - s.start for s in blk) + tuple(shape[len(blk):])


def _leaf_seq_fold(strat, slices, base_leaves, i, seed, cfg):
    acc = slices[0]
    for step, c in enumerate(slices[1:]):
        stacked = jnp.stack([acc, c])
        b = _base_leaf(base_leaves, i, acc)
        acc = strat.apply_leaf(stacked, b, leaf_index=i,
                               seed=seed + step + 1, **cfg)
    return acc


def _leaf_tree_fold(strat, slices, base_leaves, i, seed, cfg):
    level = list(slices)
    rnd = 0
    while len(level) > 1:
        nxt = []
        for j in range(0, len(level) - 1, 2):
            rnd += 1
            stacked = jnp.stack([level[j], level[j + 1]])
            b = _base_leaf(base_leaves, i, level[j])
            nxt.append(strat.apply_leaf(stacked, b, leaf_index=i,
                                        seed=seed + rnd, **cfg))
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    return level[0]


def _execute_batch(strat: Strategy, plan: MergePlan, group: List[LeafTask],
                   leaf_of, base_leaves, cache: EngineCache
                   ) -> Tuple[List[Any], List[Any]]:
    """Fused dispatch over same-dtype, same-contributor-subset
    elementwise leaves: flatten each leaf's k_i slices, concatenate
    along the element axis into one row per contribution, apply the
    leaf function ONCE on the k_i rows of N (a fold over the rows, in
    blocks of them where its chain would not fit what is free:
    `_chain_blocks`), slice the outputs back. Elementwise leaf
    functions reduce only over the k axis, so per-element arithmetic —
    and therefore output bytes — is identical to leaf-at-a-time
    execution. Returns (outputs, auxs): auxs are per-leaf fold
    accumulator slices for incremental strategies (sliced from the
    batch accumulator — elementwise, so bitwise equal to per-leaf
    folds)."""
    contributors = group[0].contributors
    ki = len(contributors)
    cfg = plan.cfg_dict()
    # each contribution's leaves, flattened and joined: a row of the
    # batch, of a shape that does not depend on k
    rows = [jnp.concatenate([leaf_of(j, t).reshape(-1) for t in group])
            for j in contributors]
    n = rows[0].shape[0]
    stack = sum(int(r.nbytes) for r in rows)
    # a row's pieces and the joined row are both live while concatenate
    # runs: account 2x, not just the output
    cache.note_stacked(2 * stack)
    if base_leaves is None:
        b = jnp.zeros((n,), rows[0].dtype)
    else:
        b = jnp.concatenate([jnp.asarray(base_leaves[t.index]).reshape(-1)
                             for t in group])
    acc = None
    if strat.fold is not None and ki >= strat.fold.min_k:
        kw = dict(strat.defaults)
        kw.update(cfg)
        # the rows are built, so they count in what is in use; the
        # fold, waited for step by step, holds buffers of one row
        planned = _chain_blocks((n,), stack // ki, rows[0])
        merged, acc = _in_blocks(
            cache, plan.strategy, group[0].path, len(group), planned,
            lambda blocks: _fold_blocks(strat.fold, rows, b, blocks,
                                        **kw))
    else:
        merged = strat.apply_leaf(jnp.stack(rows), b,
                                  leaf_index=group[0].index,
                                  seed=plan.seed, **cfg)
    outs: List[Any] = []
    auxs: List[Any] = []
    off = 0
    for t in group:
        size = math.prod(t.shape)
        outs.append(merged[off:off + size].reshape(t.shape))
        auxs.append(acc[off:off + size].reshape(t.shape)
                    if acc is not None else None)
        off += size
    return outs, auxs


# ---------------------------------------------------------------------------
# Whole-model route (legacy arithmetic + whole-model cache entry)
# ---------------------------------------------------------------------------


def model_key(strategy_name: Optional[str],
              contrib_digests: Sequence[bytes], *,
              base: Any = None, seed: int = 0,
              reduction: Optional[str] = None,
              spec: Optional[MergeSpec] = None, **cfg) -> bytes:
    spec = _as_spec(spec, strategy_name, reduction, cfg)
    strat = get_strategy(spec.strategy)
    h = hashlib.sha256(_DOMAIN_MODEL)
    k = len(contrib_digests)
    h.update(spec.cache_fragment(
        with_reduction=(strat.binary_only and k > 2)))
    h.update(pytree_digest(base) if base is not None else _NO_BASE)
    h.update(k.to_bytes(4, "big"))
    for d in contrib_digests:
        h.update(d)
    if strat.stochastic or strat.needs_key:
        h.update(str(seed).encode())
    return h.digest()


def densify_contributions(contribs: Sequence[Any],
                          coverages: Sequence[Optional[Tuple[str, ...]]],
                          base: Any) -> List[Any]:
    """Dense view of a mixed dense/sparse contribution list: each sparse
    contribution's absent leaves are filled from the base (inherit-base
    semantics). Whole-model strategies consume this — their search/
    factorization has no per-leaf structure to exploit."""
    out: List[Any] = []
    bflat = btd = None
    for c, cov in zip(contribs, coverages):
        if cov is None:
            out.append(c)
            continue
        if base is None:
            raise ValueError(
                "a sparse contribution requires a base model here: its "
                "absent leaves inherit the base (whole-model strategies "
                "operate on densified contributions)")
        if bflat is None:
            bflat = jax.tree_util.tree_flatten_with_path(base)[0]
            btd = jax.tree_util.tree_structure(base)
        pairs = jax.tree_util.tree_flatten_with_path(c)[0]
        have = {jax.tree_util.keystr(p): l for p, l in pairs}
        dense = [have.get(jax.tree_util.keystr(p), l) for p, l in bflat]
        out.append(jax.tree_util.tree_unflatten(btd, dense))
    return out


def merge(contribs: Sequence[Any], strategy_name: Optional[str] = None, *,
          contrib_ids: Optional[Sequence[str]] = None, base: Any = None,
          seed: int = 0, reduction: Optional[str] = None,
          use_cache: bool = True,
          max_batch_bytes: Optional[int] = None,
          spec: Optional[MergeSpec] = None,
          cache: Optional[EngineCache] = None,
          coverages: Optional[Sequence[Optional[Tuple[str, ...]]]]
          = None, **cfg) -> Any:
    """Merge an ORDERED contribution list through the engine.

    Byte-identical to the whole-tree reference path
    (`core.resolve.reference_apply`) on the same inputs (verified for
    all 26 registry strategies); `whole_model` strategies route through
    that path with a single whole-model cache entry. Takes a MergeSpec
    (`spec=`) or the legacy strategy-name + kwargs form. `coverages`
    marks sparse contributions (see plan_merge); whole-model strategies
    densify them with base fill first.
    """
    if not contribs:
        raise ValueError("merge() requires at least one contribution")
    spec = _as_spec(spec, strategy_name, reduction, cfg)
    cache = _cache_or_default(cache)
    strat = get_strategy(spec.strategy)
    if strat.whole_model or strat.leaf_fn is None:
        cache.stats["whole_model_dispatches"] += 1
        if coverages is not None and any(c is not None
                                         for c in coverages):
            contribs = densify_contributions(contribs, coverages, base)
        if contrib_ids is not None:
            digests = [bytes.fromhex(e) if _is_hex(e) else e.encode()
                       for e in contrib_ids]
        else:
            digests = [pytree_digest(c) for c in contribs]
        key = model_key(None, digests, base=base, seed=seed, spec=spec)
        if use_cache:
            hit = cache.get(key)
            if hit is not None:
                cache.stats["hits"] += 1
                return hit
            cache.stats["misses"] += 1
        from repro.core.resolve import reference_apply
        with span("engine.whole_model", strategy=spec.strategy,
                  k=len(contribs)):
            out = reference_apply(spec.strategy, list(contribs), base=base,
                                  seed=seed, reduction=spec.reduction,
                                  **spec.cfg_dict())
        if use_cache:
            nbytes = sum(int(l.nbytes)
                         for l in jax.tree_util.tree_leaves(out))
            cache.put(key, out, nbytes)
        return out
    cache.stats["planned_merges"] += 1
    plan = plan_for(contribs, contrib_ids=contrib_ids,
                    base=base, seed=seed, spec=spec,
                    coverages=coverages, obs=cache.obs)
    return execute_plan(plan, contribs, base=base, use_cache=use_cache,
                        max_batch_bytes=max_batch_bytes, cache=cache)


def _is_hex(s: str) -> bool:
    try:
        bytes.fromhex(s)
        return len(s) % 2 == 0 and len(s) > 0
    except ValueError:
        return False
