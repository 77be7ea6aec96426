"""Tier-1 algebraic audit: raw Phase 1 must match paper Table 3 EXACTLY;
Phase 2 through CRDTMergeState must be 26/26 x 4 = 104/104 (Table 4).
Plus the Proposition 4 counterexamples from the paper text."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.properties import (
    audit_all_raw, audit_all_wrapped, audit_raw, audit_wrapped,
    controlled_tensors, TABLE3_EXPECTED)
from repro.strategies import get_strategy, list_strategies


@pytest.fixture(scope="module")
def x64():
    with jax.enable_x64(True):
        yield


@pytest.fixture(scope="module")
def tensors(x64):
    return controlled_tensors(9, dtype=jnp.float64)


def test_all_26_strategies_registered():
    assert len(list_strategies()) == 26
    assert set(list_strategies()) == set(TABLE3_EXPECTED)


# ---------------------------------------------------------------------------
# cfg schema audit (repro.api MergeSpec validation contract)
# ---------------------------------------------------------------------------


def _signature_schema(strat):
    """The schema implied by the leaf function's keyword signature:
    every defaulted parameter after the positional (s, b[, key])
    tensors. This is ground truth for what the strategy consumes."""
    import inspect
    sig = inspect.signature(strat.leaf_fn)
    skip = 3 if strat.needs_key else 2
    schema = {}
    for i, (pname, p) in enumerate(sig.parameters.items()):
        if i < skip or p.kind is inspect.Parameter.VAR_KEYWORD:
            continue
        schema[pname] = (type(p.default), p.default)
    return schema


@pytest.mark.parametrize("name", sorted(TABLE3_EXPECTED))
def test_declared_cfg_schema_matches_leaf_signature(name):
    """Every catalog strategy declares a cfg schema, and the declaration
    mirrors the leaf function's keyword signature exactly — names,
    types, AND default values. (Defaults matter doubly: MergeSpec
    canonicalizes declared defaults into the digest, so a drifted
    default would silently change both cache keys and outputs.)"""
    strat = get_strategy(name)
    assert strat.cfg_schema is not None, f"{name} declares no cfg schema"
    assert strat.cfg_schema == _signature_schema(strat), name


def test_schemas_cover_audit_kwargs():
    """The kwargs this audit suite itself exercises are all declared."""
    assert "trim" in get_strategy("ties").cfg_schema
    assert "t" in get_strategy("slerp").cfg_schema
    assert "lam" in get_strategy("task_arithmetic").cfg_schema
    from repro.api import MergeSpec, SpecError
    with pytest.raises(SpecError, match="did you mean 'trim'"):
        MergeSpec("ties", {"tirm": 0.2})
    with pytest.raises(SpecError, match="did you mean 'p_min'"):
        MergeSpec("della", {"p_mn": 0.2})
    assert MergeSpec("slerp", {"t": 0.3}).cfg_dict()["t"] == 0.3


@pytest.mark.parametrize("name", sorted(TABLE3_EXPECTED))
def test_table3_raw_pattern(name, tensors):
    r = audit_raw(name, tensors)
    exp_c, exp_a, exp_i = TABLE3_EXPECTED[name]
    assert r.commutative == exp_c, f"{name} commutativity"
    assert r.associative == exp_a, f"{name} associativity"
    assert r.idempotent == exp_i, f"{name} idempotency"


def test_table3_totals(tensors):
    res = audit_all_raw(tensors)
    assert sum(r.commutative for r in res.values()) == 21
    assert sum(r.associative for r in res.values()) == 1
    assert sum(r.idempotent for r in res.values()) == 14
    assert sum(r.crdt for r in res.values()) == 0      # paper: 0/26


@pytest.mark.parametrize("name", sorted(TABLE3_EXPECTED))
def test_table4_wrapped_pass(name, tensors):
    r = audit_wrapped(name, tensors)
    assert r.commutative and r.associative and r.idempotent and \
        r.convergent, f"{name} fails CRDT-wrapped properties"


def test_phase2_is_104_of_104(tensors):
    res = audit_all_wrapped(tensors)
    total = sum(r.commutative + r.associative + r.idempotent + r.convergent
                for r in res.values())
    assert total == 104


# ---------------------------------------------------------------------------
# Incremental-fold audit: a claimed fold must be bit-equal to the full
# per-leaf recompute, from every valid resumption point
# ---------------------------------------------------------------------------


INCREMENTAL_EXPECTED = {"linear", "negative_merge", "task_arithmetic",
                        "weight_average"}


def test_incremental_capability_set_is_exact():
    """Exactly the strategies whose canonical per-leaf math is a
    sequential fold declare the capability — no silent additions (every
    claim must be proven below) and no silent removals (the engine's
    O(changed) resumption depends on these)."""
    claimed = {n for n in list_strategies() if get_strategy(n).incremental}
    assert claimed == INCREMENTAL_EXPECTED


@pytest.mark.parametrize("name", sorted(TABLE3_EXPECTED))
def test_incremental_claim_proven_bitwise(name):
    """Every strategy claiming `incremental` must prove its fold:
    (a) the fold-driven recompute is bit-equal to the strategy's own
    leaf function at every prefix length k >= fold.min_k, and
    (b) resuming from the cached accumulator of every valid prefix
    m in [min_k, k) over the new tail is bit-equal to the full
    recompute at k. A strategy without the claim must declare no fold.
    This is the audit bench_sparse and the engine's prefix-fold
    resumption rely on — an unproven claim fails here, not in prod."""
    from repro.strategies.base import run_fold
    strat = get_strategy(name)
    if not strat.incremental:
        assert strat.fold is None
        return
    fold = strat.fold
    rng = np.random.default_rng(17)
    stacked = jnp.asarray(rng.standard_normal((6, 4, 4)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((4, 4)), jnp.float32)
    cfg = dict(strat.defaults)
    for k in range(fold.min_k, 7):
        full = strat.apply_leaf(stacked[:k], b)
        direct, _ = run_fold(fold, stacked[:k], b, **cfg)
        assert full.dtype == direct.dtype, name
        assert np.asarray(full).tobytes() == np.asarray(direct).tobytes(), \
            f"{name}: fold != leaf_fn at k={k}"
        for m in range(fold.min_k, k):
            _, acc = run_fold(fold, stacked[:m], b, finalize=False, **cfg)
            resumed, _ = run_fold(fold, stacked[m:k], b, acc=acc, k=k,
                                  **cfg)
            assert np.asarray(full).tobytes() == \
                np.asarray(resumed).tobytes(), \
                f"{name}: resume from m={m} at k={k} not bit-equal"


def test_linear_min_k_guards_the_interpolation_regime():
    """`linear` interpolates at k == 2 (a different formula), so its
    fold declares min_k=3: the k == 2 output must NOT be the fold's
    output, or the guard is vacuous. (At t=0.5 the two happen to agree
    bitwise — halving is exact — so probe at t=0.3.)"""
    from repro.strategies.base import run_fold
    strat = get_strategy("linear")
    assert strat.fold.min_k == 3
    rng = np.random.default_rng(23)
    stacked = jnp.asarray(rng.standard_normal((2, 4, 4)), jnp.float32)
    b = jnp.zeros((4, 4), jnp.float32)
    via_leaf = strat.apply_leaf(stacked, b, t=0.3)
    via_fold, _ = run_fold(strat.fold, stacked, b, t=0.3)
    assert np.asarray(via_leaf).tobytes() != np.asarray(via_fold).tobytes()


# ---------------------------------------------------------------------------
# Proposition 4 concrete counterexamples (paper §3.2)
# ---------------------------------------------------------------------------


def test_weight_average_eqs_4_5(x64):
    """f(f(a,b),c) = (a+b+2c)/4 vs f(a,f(b,c)) = (2a+b+c)/4."""
    s = get_strategy("weight_average")
    a, b, c = (jnp.asarray(x, jnp.float64)
               for x in np.random.default_rng(1).standard_normal((3, 4, 4)))
    left = s([s([a, b]), c])
    right = s([a, s([b, c])])
    assert jnp.allclose(left, (a + b + 2 * c) / 4)
    assert jnp.allclose(right, (2 * a + b + c) / 4)
    assert not jnp.allclose(left, right)


def test_slerp_unit_vector_counterexample(x64):
    """Paper: e1,e2,e3 -> left ~ (.5,.5,.707), right ~ (.707,.5,.5)."""
    s = get_strategy("slerp")
    v1 = jnp.asarray([1.0, 0.0, 0.0], jnp.float64)
    v2 = jnp.asarray([0.0, 1.0, 0.0], jnp.float64)
    v3 = jnp.asarray([0.0, 0.0, 1.0], jnp.float64)
    left = s([s([v1, v2]), v3])
    right = s([v1, s([v2, v3])])
    assert jnp.allclose(left, jnp.asarray([0.5, 0.5, np.sqrt(0.5)]),
                        atol=1e-9)
    assert jnp.allclose(right, jnp.asarray([np.sqrt(0.5), 0.5, 0.5]),
                        atol=1e-9)
    assert not jnp.allclose(left, right)


def test_slerp_commutative_only_at_half(x64):
    s = get_strategy("slerp")
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal(16), jnp.float64)
    b = jnp.asarray(rng.standard_normal(16), jnp.float64)
    assert jnp.allclose(s([a, b], t=0.5), s([b, a], t=0.5), atol=1e-9)
    assert not jnp.allclose(s([a, b], t=0.3), s([b, a], t=0.3), atol=1e-5)


def test_ties_thresholding_counterexample(x64):
    """Thresholding breaks associativity (paper's 3-vector example shape)."""
    s = get_strategy("ties")
    a = jnp.asarray([10.0, 1.0, 0.1], jnp.float64)
    b = jnp.asarray([0.1, 10.0, 1.0], jnp.float64)
    c = jnp.asarray([1.0, 0.1, 10.0], jnp.float64)
    left = s([s([a, b], trim=1 / 3), c], trim=1 / 3)
    right = s([a, s([b, c], trim=1 / 3)], trim=1 / 3)
    assert not jnp.allclose(left, right, atol=1e-6)


def test_task_arithmetic_associative_but_not_idempotent(x64):
    s = get_strategy("task_arithmetic")
    rng = np.random.default_rng(5)
    a, b, c = (jnp.asarray(x, jnp.float64)
               for x in rng.standard_normal((3, 4, 4)))
    left = s([s([a, b]), c])
    right = s([a, s([b, c])])
    assert jnp.allclose(left, right, atol=1e-9)        # associative
    assert not jnp.allclose(s([a, a]), a, atol=1e-5)   # not idempotent


# ---------------------------------------------------------------------------
# Production-shape (Tier-2 style) checks on synthetic weights
# ---------------------------------------------------------------------------


def test_tier2_slices_wrapped_pass():
    from repro.core.properties import production_slices
    from repro.configs import get_config
    base, tensors = production_slices(get_config("minitron-8b"), n=9,
                                      slice_dim=128)
    for name in ("weight_average", "ties", "dare", "slerp",
                 "task_arithmetic", "fisher_merge"):
        r = audit_wrapped(name, tensors, base=base)
        assert r.crdt, f"{name} fails wrapped at 128x128"


def test_cross_resolution_consistency():
    """The paper's 128 vs 512 cross-resolution check (§6.3): our wrapped
    architecture must agree bitwise at BOTH resolutions."""
    from repro.core.properties import production_slices
    from repro.configs import get_config
    cfg = get_config("minitron-8b")
    for dim in (128, 512):
        base, tensors = production_slices(cfg, n=9, slice_dim=dim)
        r = audit_wrapped("ada_merging", tensors, base=base)
        assert r.crdt, f"ada_merging wrapped fails at {dim}x{dim}"
