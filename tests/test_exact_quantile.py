"""`strategies.select.exact_quantile_rows` against `jnp.quantile`.

The counting selection must return `jnp.quantile(a, q, axis=1,
keepdims=True)` bit for bit, so that every strategy that takes an exact
quantile of magnitudes (TIES's quantile trim, model_breadcrumbs, emr)
merges to the same bytes as before; and the executor counts each leaf
whose exact quantile it took on the resolving replica's registry.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import MergeSpec, Replica
from repro.strategies import get_strategy
from repro.strategies.select import (
    exact_quantile_rows, quantile_path, recording, SELECT, SORT)

DTYPES = [jnp.bfloat16, jnp.float16, jnp.float32]
SHAPES = [(1, 1), (3, 1), (3, 2), (3, 7), (3, 4099)]
QS = [0.0, 0.2, 0.5, 1 - 1e-7, 1.0]
KINDS = ["duplicates", "zeros", "subnormal", "inf", "nan"]


def _rows(dtype, shape, kind, seed=0):
    """Non-negative rows: bf16-rounded |N(0, 0.002)| (heavy duplicates),
    all zeros, subnormals of the dtype, a +inf in every row, or a NaN in
    the first row beside clean rows. Made on the host in the target
    dtype, so that no device conversion flushes the subnormals."""
    host = np.dtype(jnp.dtype(dtype))
    x = np.abs(np.random.default_rng(seed).normal(0, 0.002, shape))
    x = x.astype(np.dtype(jnp.bfloat16)).astype(np.float64)
    if kind == "zeros":
        x[:] = 0.0
    elif kind == "subnormal":
        x = x / 0.01 * float(jnp.finfo(dtype).tiny) / 4   # below normal
    x = x.astype(host)
    if kind == "inf":
        x[:, shape[1] // 2] = np.inf
    elif kind == "nan":
        x[0, shape[1] - 1] = np.nan
    return jnp.asarray(x)


def _bits(x):
    x = np.asarray(x)
    return x.dtype, x.shape, x.tobytes()


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
def test_matches_jnp_quantile_bitwise(dtype, shape, kind):
    a = _rows(dtype, shape, kind)
    if kind == "subnormal":
        assert np.any(np.asarray(a, np.float64) > 0)
    assert quantile_path(a.dtype, shape[1]) == SELECT
    for q in QS:
        want = jnp.quantile(a, q, axis=1, keepdims=True)
        got = exact_quantile_rows(a, q)
        assert _bits(got) == _bits(want), (q, np.asarray(got),
                                           np.asarray(want))


def test_fallback_path_matches_and_is_recorded():
    """A dtype the descent does not take (f64 under x64) and a row of
    2**31 elements or more go to `jnp.quantile`."""
    assert quantile_path(jnp.bfloat16, 2 ** 31) == SORT
    assert quantile_path(jnp.float32, 2 ** 31 - 1) == SELECT
    assert quantile_path(jnp.int32, 8) == SORT
    with jax.enable_x64(True):
        a = jnp.abs(jnp.asarray(
            np.random.default_rng(1).normal(0, 0.002, (3, 257))))
        assert a.dtype == jnp.float64
        with recording() as taken:
            for q in QS:
                assert _bits(exact_quantile_rows(a, q)) == _bits(
                    jnp.quantile(a, q, axis=1, keepdims=True))
        assert taken == [SORT] * len(QS)
    with recording() as taken:
        exact_quantile_rows(_rows(jnp.bfloat16, (3, 7), "duplicates"), 0.2)
    assert taken == [SELECT]


# ------------------------------------------ the strategies, before and after

_EPS = 1e-12


def _fl(x):
    return x.reshape(x.shape[0], -1)


def _old_ties(s, b, trim=0.2, **kw):
    tau = _fl(s - b)
    a = jnp.abs(tau)
    q = jnp.quantile(a, trim, axis=1, keepdims=True)
    trimmed = tau * (a >= q).astype(tau.dtype)
    elected = jnp.sign(jnp.sum(trimmed, axis=0, keepdims=True))
    agree = (jnp.sign(trimmed) == elected) & (trimmed != 0)
    agree = agree.astype(tau.dtype)
    cnt = jnp.maximum(jnp.sum(agree, axis=0), 1.0)
    merged = jnp.sum(trimmed * agree, axis=0) / cnt
    return b + merged.reshape(s.shape[1:])


def _old_model_breadcrumbs(s, b, beta=0.1, gamma=0.1, **kw):
    tau = _fl(s - b)
    a = jnp.abs(tau)
    qlo = jnp.quantile(a, beta, axis=1, keepdims=True)
    qhi = jnp.quantile(a, 1.0 - gamma, axis=1, keepdims=True)
    mask = ((a >= qlo) & (a <= qhi)).astype(tau.dtype)
    merged = jnp.mean(tau * mask, axis=0)
    return b + merged.reshape(s.shape[1:])


def _old_emr(s, b, trim=0.1, **kw):
    tau = _fl(s - b)
    elected = jnp.sign(jnp.sum(tau, axis=0, keepdims=True))
    mask = (jnp.sign(tau) == elected).astype(tau.dtype)
    m = jnp.sum(tau * mask, axis=0) / jnp.maximum(jnp.sum(mask, axis=0), 1.0)
    q = jnp.quantile(jnp.abs(m), trim)
    m = m * (jnp.abs(m) >= q)
    norms = jnp.sqrt(jnp.sum(_fl(s - b) ** 2, axis=1)) + _EPS
    rho = jnp.mean(norms) / (jnp.linalg.norm(m) + _EPS)
    return b + (rho * m).reshape(s.shape[1:])


OLD = {"ties": _old_ties, "model_breadcrumbs": _old_model_breadcrumbs,
       "emr": _old_emr}


@pytest.mark.parametrize("leaf", [(64, 96), (48,)], ids=["64x96", "48"])
@pytest.mark.parametrize("name", sorted(OLD))
def test_strategy_leaf_bytes_unchanged(name, leaf):
    rng = np.random.default_rng(7)
    base = jnp.asarray(rng.normal(0, 0.02, leaf), jnp.bfloat16)
    s = jnp.asarray(np.asarray(base, np.float32)
                    + rng.normal(0, 0.002, (3,) + leaf), jnp.bfloat16)
    got = get_strategy(name).apply_leaf(s, base)
    assert _bits(got) == _bits(OLD[name](s, base))


@pytest.mark.parametrize("name", sorted(OLD))
def test_executor_counts_select_per_leaf(name):
    """Through `Replica`: every executed leaf counts once on `select`
    (breadcrumbs' two quantiles of a leaf too), cache hits count
    nothing, and nothing counts on `sort`."""
    rng = np.random.default_rng(3)

    def model():
        return {"w": jnp.asarray(rng.normal(0, 0.02, (64, 96)), jnp.bfloat16),
                "v": jnp.asarray(rng.normal(0, 0.02, (48,)), jnp.bfloat16),
                "f": jnp.asarray(rng.normal(0, 0.02, (5, 7)), jnp.float32)}

    rep = Replica("q")
    for _ in range(3):
        rep.contribute(model())
    rep.resolve(MergeSpec(name))
    rep.resolve(MergeSpec(name))                 # all cache hits
    rep.contribute(model())
    rep.resolve(MergeSpec(name))                 # every leaf again
    m = rep.metrics()
    assert m["exact_quantile_leaves_total{path=select}"] == 2 * 3
    assert m.get("exact_quantile_leaves_total{path=sort}", 0.0) == 0.0
