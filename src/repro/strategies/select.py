"""Exact per-row quantiles of non-negative floats, by counting.

`jnp.quantile(a, q, axis=1, keepdims=True)` sorts every row of `a` and
gathers two elements of the sorted row. `exact_quantile_rows` finds the
same two elements (ranks `floor(q (n-1))` and `ceil(q (n-1))`) without
a sort, and interpolates them with `jnp.quantile`'s own float ops, so
the result is bit for bit `jnp.quantile`'s.

For a non-negative IEEE float (sign bit 0, NaN included) the bit
pattern read as a signed integer orders as the value does. The rank-r
element of a row is therefore the largest key v with
count(key < v) <= r, and a most-significant-first descent over the
key's bits finds it: 15 steps for bf16/f16, 31 for f32, each one fused
compare-and-count over the row for both ranks together. A row holding
a NaN gives NaN, as `jnp.quantile` does.

The descent runs for bf16, f16 and f32 rows of fewer than 2**31
elements (its counts are int32); any other input is passed to
`jnp.quantile`. `quantile_path` says which of the two an input takes.

`recording()` lets the executor count the paths a leaf took without
passing anything through the leaf functions' hashed configuration.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

SELECT = "select"
SORT = "sort"

# bits of the key below the sign bit, by float dtype
_KEY_BITS = {jnp.dtype(jnp.bfloat16): 15, jnp.dtype(jnp.float16): 15,
             jnp.dtype(jnp.float32): 31}
_KEY_DTYPE = {15: jnp.int16, 31: jnp.int32}

_TAKEN: contextvars.ContextVar[Optional[List[str]]] = \
    contextvars.ContextVar("exact_quantile_taken", default=None)


def quantile_path(dtype, n: int) -> str:
    """`SELECT` where the counting descent takes a row of `n` elements
    of `dtype`, else `SORT` (`jnp.quantile`)."""
    if jnp.dtype(dtype) in _KEY_BITS and 0 < n < 2 ** 31:
        return SELECT
    return SORT


@contextlib.contextmanager
def recording() -> Iterator[List[str]]:
    """Collect the path of every `exact_quantile_rows` call made inside
    the block (in this context), in call order. Calls inside a traced
    function record once, when it is traced."""
    taken: List[str] = []
    token = _TAKEN.set(taken)
    try:
        yield taken
    finally:
        _TAKEN.reset(token)


def exact_quantile_rows(a, q: float):
    """`jnp.quantile(a, q, axis=1, keepdims=True)` (method "linear") of
    a non-negative `[k, n]` float array, bit for bit, without a sort
    where `quantile_path` allows."""
    a = jnp.asarray(a)
    path = quantile_path(a.dtype, a.shape[1])
    taken = _TAKEN.get()
    if taken is not None:
        taken.append(path)
    if path == SORT:
        return jnp.quantile(a, q, axis=1, keepdims=True)
    return _select_quantile(a, q)


@jax.jit
def _select_quantile(a, q):
    # q arrives as jnp.quantile's does (a traced scalar of the default
    # float dtype, not a constant), and the ranks and weights come from
    # jnp.quantile's ops on it
    n = lax.convert_element_type(a.shape[1], q.dtype)
    q = lax.mul(q, n - 1)
    low = lax.floor(q)
    high = lax.ceil(q)
    high_weight = lax.sub(q, low)
    low_weight = lax.sub(jnp.ones_like(high_weight), high_weight)
    low = lax.clamp(jnp.zeros_like(low), low, n - 1)
    high = lax.clamp(jnp.zeros_like(high), high, n - 1)
    if a.shape[1] == 1:
        # both ranks are the one element, and jnp.quantile gathers the
        # same value twice: give XLA that same value, so that it
        # simplifies the interpolation as there (on a TPU, an inf row
        # then stays inf where the weight of one side is 0)
        value = jnp.where(jnp.isnan(a), jnp.nan, a)
        return _interpolate(value, value, low_weight, high_weight, a.dtype)

    # per row, the largest key v with count(key < v) <= rank, for both
    # ranks: one bit a step, the most significant first, both ranks
    # counted in one pass over the row (the first pass also counts NaNs)
    bits = _KEY_BITS[a.dtype]
    keys = lax.bitcast_convert_type(a, _KEY_DTYPE[bits])
    ranks = (lax.convert_element_type(low, jnp.int32),
             lax.convert_element_type(high, jnp.int32))
    found = [jnp.zeros((a.shape[0],), jnp.int32) for _ in ranks]
    nans = None
    for bit in range(bits - 1, -1, -1):
        cands = [v | (1 << bit) for v in found]
        preds = [keys < c.astype(keys.dtype)[:, None] for c in cands]
        if nans is None:
            *counts, nans = _row_counts(preds + [jnp.isnan(a)])
        else:
            counts = _row_counts(preds)
        found = [jnp.where(cnt <= r, c, v)
                 for v, c, cnt, r in zip(found, cands, counts, ranks)]
    low_value, high_value = (
        lax.bitcast_convert_type(v.astype(keys.dtype), a.dtype)[:, None]
        for v in found)

    has_nan = (nans > 0)[:, None]
    low_value = jnp.where(has_nan, jnp.nan, low_value)
    high_value = jnp.where(has_nan, jnp.nan, high_value)
    return _interpolate(low_value, high_value, low_weight, high_weight,
                        a.dtype)


def _interpolate(low_value, high_value, low_weight, high_weight, dtype):
    # jnp.quantile's "linear" method, op for op
    result = lax.add(lax.mul(low_value.astype(low_weight.dtype), low_weight),
                     lax.mul(high_value.astype(high_weight.dtype),
                             high_weight))
    return lax.convert_element_type(result, dtype)


def _row_counts(preds):
    """Per row, the number of true entries of each `[k, n]` predicate,
    as int32, from one variadic reduction (one read of the row)."""
    ops = tuple(p.astype(jnp.int32) for p in preds)
    init = tuple(jnp.int32(0) for _ in ops)
    return lax.reduce(ops, init,
                      lambda xs, ys: tuple(x + y for x, y in zip(xs, ys)),
                      (1,))
