"""Strategy interface.

A strategy is an n-ary pure function over an ORDERED list of contribution
pytrees (paper Assumption 9): σ(contribs, base, seed, **cfg) -> merged.
All randomness must flow from `seed` (Phase 2 derives it from the Merkle
root; the raw Phase-1 audit feeds varying seeds to reflect default
stochastic behaviour, per paper Appendix F).

Two execution protocols share one registration:

  * whole-tree (`__call__`): stack k full pytrees and run `fn` — the
    legacy path, and the only route for `whole_model=True` strategies
    (population search, SVD factorizations) whose cost profile is not
    per-tensor;
  * leafwise (`apply_leaf`): the planner/executor engine
    (`core/engine`) calls `leaf_fn` one tensor at a time, deriving the
    per-leaf PRNG key from the *global* flatten index exactly as
    `leafwise` does — so engine output is byte-identical to `__call__`.

`elementwise=True` marks leaf functions that reduce only over the
leading k axis (no per-leaf norms/quantiles/shape use): the engine may
fuse many such leaves into one flattened [k, N] dispatch without
changing any output byte.

`cfg_schema` declares every configuration knob the strategy consumes —
``{name: (type, default)}`` — so `repro.api.MergeSpec` can reject
unknown or ill-typed kwargs at construction (the legacy ``**cfg``
surface silently dropped them at merge time). The audit suite asserts
each catalog strategy's schema matches its leaf function's signature
exactly, names and defaults both.

Algebraically incremental strategies additionally declare a `LeafFold`:
an explicit left fold (init / step / finalize) over the ordered
contribution list of ONE leaf. The fold IS the canonical computation —
`run_fold` drives both the full recompute inside `leaf_fn` and the
engine's `fold_update` resumption, so "fold result bit-equal to full
recompute" holds by construction rather than by relying on XLA
reduction order (jnp.sum/jnp.mean reassociate; a resumed fold would
not). The audit suite enforces the contract for every strategy that
claims `incremental`.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class LeafFold:
    """Sequential left fold defining an incremental strategy's per-leaf
    math: acc = init(x_0); acc = step(acc, x_j) for j = 1..k-1;
    out = finalize(acc, k). The accumulator is float32 (promoted from
    the input dtype) and strictly sequential in canonical contribution
    order, so a cached accumulator extends with new contributions to a
    bit-identical result (`run_fold(..., acc=cached, start=m)`).

    `min_k` guards regime switches: a fold is only valid when the full
    recompute at every prefix length >= min_k takes the fold path (e.g.
    `linear` interpolates at k == 2 — a different formula — so its fold
    declares min_k=3 and the engine will not resume from a k == 2
    cache entry).
    """
    init: Callable      # init(x0, base, **cfg) -> acc (float32)
    step: Callable      # step(acc, x, base, **cfg) -> acc
    finalize: Callable  # finalize(acc, k, base, dtype, **cfg) -> leaf
    min_k: int = 1


def run_fold(fold: LeafFold, stacked, base, *, acc=None, start: int = 0,
             finalize: bool = True, k: Optional[int] = None, **cfg):
    """Drive a LeafFold over stacked[start:k]. This single driver is the
    one place incremental math executes — the catalog's `leaf_fn`s call
    it for the full recompute and the engine calls it to resume from a
    cached accumulator, which is what makes the two bit-equal.

    `stacked` is whatever slice of the ordered contribution list is at
    hand ([k, ...] array or list of leaves): a full recompute passes all
    k leaves and no `acc`; a resumption passes only the NEW leaves plus
    the cached `acc` and the TOTAL count via `k=` (finalize needs the
    true k, e.g. the mean divisor).

    Returns (value_or_None, acc): `acc` is the raw accumulator (reusable
    for resumption); `value` is finalize(acc, k) when requested.
    """
    i = start
    if acc is None:
        acc = fold.init(jnp.asarray(stacked[i], jnp.float32), base, **cfg)
        i += 1
    while i < len(stacked):
        acc = fold.step(acc, jnp.asarray(stacked[i], jnp.float32),
                        base, **cfg)
        i += 1
    if not finalize:
        return None, acc
    total = (len(stacked) - start) if k is None else k
    dtype = jnp.asarray(stacked[0]).dtype
    return fold.finalize(acc, total, base, dtype, **cfg), acc


@dataclass(frozen=True)
class Strategy:
    name: str
    fn: Callable                 # fn(stacked_tree, base_tree, seed, **cfg)
    stochastic: bool = False
    binary_only: bool = False
    category: str = "linear"          # linear | sparse | geometry | search
    defaults: Dict[str, Any] = field(default_factory=dict)
    leaf_fn: Optional[Callable] = None  # leaf_fn(stacked[k,...], base, [key])
    needs_key: bool = False           # leaf_fn consumes a PRNG key
    whole_model: bool = False         # not per-tensor: legacy path only
    elementwise: bool = False         # reduces only over the k axis
    # declared cfg knobs: {name: (type, default)}. None = undeclared
    # (strict MergeSpec construction then rejects any cfg at all).
    cfg_schema: Optional[Dict[str, Tuple[type, Any]]] = None
    # algebraic incremental fold; None = full per-leaf recompute only.
    # The audit suite proves every declared fold bit-equal to the full
    # recompute at all prefix lengths >= fold.min_k.
    fold: Optional[LeafFold] = None

    def __call__(self, contribs: List[Any], *, base: Any = None,
                 seed: int = 0, **cfg) -> Any:
        if len(contribs) < 1:
            raise ValueError(
                f"strategy {self.name!r} requires at least one "
                "contribution, got an empty list")
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(list(xs)), *contribs)
        if base is None:
            base = jax.tree_util.tree_map(jnp.zeros_like, contribs[0])
        kw = dict(self.defaults)
        kw.update(cfg)
        return self.fn(stacked, base, seed, **kw)

    def apply_leaf(self, stacked, base, *, leaf_index: int = 0,
                   seed: int = 0, **cfg) -> Any:
        """Merge ONE leaf: stacked [k, ...] slices + base leaf.

        Key derivation replicates `leafwise` exactly —
        `fold_in(PRNGKey(seed & 0x7FFFFFFF), leaf_index)` with the
        global flatten index — so per-leaf execution is byte-identical
        to the whole-tree path.
        """
        if self.leaf_fn is None:
            raise TypeError(f"strategy {self.name!r} has no leafwise "
                            "executor (whole-model only)")
        kw = dict(self.defaults)
        kw.update(cfg)
        if self.needs_key:
            key = jax.random.fold_in(
                jax.random.PRNGKey(seed & 0x7FFFFFFF), leaf_index)
            return self.leaf_fn(stacked, base, key, **kw)
        return self.leaf_fn(stacked, base, **kw)

    @property
    def batchable(self) -> bool:
        """True when leaves may be fused into one flattened dispatch
        without changing output bytes: elementwise arithmetic, no
        per-leaf key, no per-leaf fold structure."""
        return (self.elementwise and not self.needs_key
                and not self.binary_only and self.leaf_fn is not None)

    @property
    def incremental(self) -> bool:
        """True when the strategy declares an audited algebraic fold:
        the engine may extend a cached per-leaf accumulator with new
        contributions instead of recomputing over all k, bit-equal to
        the full recompute by the LeafFold contract."""
        return self.fold is not None


REGISTRY: Dict[str, Strategy] = {}


def register(strategy: Strategy) -> Strategy:
    REGISTRY[strategy.name] = strategy
    return strategy


def get_strategy(name: str) -> Strategy:
    if name not in REGISTRY:
        raise KeyError(f"unknown strategy {name!r}; have {sorted(REGISTRY)}")
    return REGISTRY[name]


def list_strategies() -> List[str]:
    return sorted(REGISTRY)


def leafwise(leaf_fn: Callable, needs_key: bool = False) -> Callable:
    """Lift a per-leaf function (stacked [k,...], base, [key]) -> leaf.

    Each leaf is waited for before the next is enqueued, so on an
    asynchronous device one leaf's transients are live at a time."""
    def nary(stacked, base, seed, **cfg):
        leaves_s, treedef = jax.tree_util.tree_flatten(stacked)
        leaves_b = treedef.flatten_up_to(base)
        outs = []
        for i, (sl, bl) in enumerate(zip(leaves_s, leaves_b)):
            if needs_key:
                key = jax.random.fold_in(
                    jax.random.PRNGKey(seed & 0x7FFFFFFF), i)
                outs.append(leaf_fn(sl, bl, key, **cfg))
            else:
                outs.append(leaf_fn(sl, bl, **cfg))
            jax.block_until_ready(outs[-1])
        return jax.tree_util.tree_unflatten(treedef, outs)
    return nary
