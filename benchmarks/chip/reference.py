"""Plain reference of what one merge round must produce.

Written from the definitions, in plain `jax.numpy`, and importing
nothing of the system under test:

  * content ids: SHA-256 over each leaf's canonical bytes (dtype, shape,
    row-major data), leaves taken in sorted key-path order (the paper's
    content address, Assumption 11);
  * the Merkle root over the sorted ids (pairwise SHA-256 of
    `0x01 | left | right`, an odd node promoted; paper section 4.2);
  * the merges, one leaf at a time, over the contributions in canonical
    order (ascending content id):
      - `weight_average`: a float32 left fold of the contributions, then
        divided by k and rounded to the weights' dtype;
      - `task_arithmetic` (Ilharco et al. 2023, arXiv:2212.04089): a
        float32 left fold of the task vectors `c - base`, then
        `base + lam * sum`, rounded to the weights' dtype;
      - `ties` (Yadav et al. 2023, arXiv:2306.01708): task vectors
        `c - base`, each trimmed below its `trim` quantile of |tau|
        (linear interpolation between the sorted neighbours, in
        float32), the sign elected by the sum of the trimmed vectors,
        the disjoint mean of the agreeing entries added to the base.
        Its arithmetic is in the weights' dtype, as the configuration
        states; each step runs as its own operation, so every
        intermediate is rounded to that dtype.

A contribution may be sparse (a partial pytree): each leaf of the model
merges over only the contributions that carry it, and a leaf that none
carries is the base leaf.

`quantize` gives the control: the same reference fed int8 copies of
its inputs (per-leaf symmetric scale), the precision below bfloat16.
"""
from __future__ import annotations

import hashlib
from typing import Dict, Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_EMPTY_ROOT = hashlib.sha256(b"crdt-merge/empty").digest()


def leaves_with_paths(tree) -> List[tuple]:
    """(key path string, leaf) in sorted key-path order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return sorted(((jax.tree_util.keystr(p), leaf) for p, leaf in flat),
                  key=lambda kv: kv[0])


def content_id(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in leaves_with_paths(tree):
        a = np.asarray(leaf)
        leaf_hash = hashlib.sha256()
        leaf_hash.update(f"{a.dtype}|{a.shape}|".encode())
        leaf_hash.update(np.ascontiguousarray(a).tobytes())
        h.update(path.encode())
        h.update(leaf_hash.digest())
    return h.hexdigest()


def merkle_root(ids: Sequence[str]) -> bytes:
    level = sorted(bytes.fromhex(i) for i in ids)
    if not level:
        return _EMPTY_ROOT
    while len(level) > 1:
        nxt = [hashlib.sha256(b"\x01" + level[i] + level[i + 1]).digest()
               for i in range(0, len(level) - 1, 2)]
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    return level[0]


def _weight_average(xs: List[jax.Array], base, dtype) -> jax.Array:
    acc = xs[0].astype(jnp.float32)
    for x in xs[1:]:
        acc = acc + x.astype(jnp.float32)
    return (acc / len(xs)).astype(dtype)


def _trim_threshold(a: jax.Array, trim: float) -> jax.Array:
    """Per-row `trim` quantile of a [k, n] array, linear interpolation."""
    n = a.shape[1]
    srt = jnp.sort(a, axis=1)
    pos = jnp.float32(trim) * (jnp.float32(n) - 1)
    lo = jnp.floor(pos)
    w_hi = pos - lo
    lo_i = int(lo)
    hi_i = min(int(jnp.ceil(pos)), n - 1)
    t = (srt[:, lo_i:lo_i + 1].astype(jnp.float32) * (1 - w_hi)
         + srt[:, hi_i:hi_i + 1].astype(jnp.float32) * w_hi)
    return t.astype(a.dtype)


def _ties(xs: List[jax.Array], base, dtype, trim: float = 0.2
          ) -> jax.Array:
    k = len(xs)
    tau = jnp.stack([x - base for x in xs]).reshape(k, -1)
    mag = jnp.abs(tau)
    keep = (mag >= _trim_threshold(mag, trim)).astype(tau.dtype)
    trimmed = tau * keep
    elected = jnp.sign(jnp.sum(trimmed, axis=0, keepdims=True))
    agree = ((jnp.sign(trimmed) == elected) & (trimmed != 0)).astype(
        tau.dtype)
    count = jnp.maximum(jnp.sum(agree, axis=0), 1.0)
    merged = jnp.sum(trimmed * agree, axis=0) / count
    return base + merged.reshape(base.shape)


def _task_arithmetic(xs: List[jax.Array], base, dtype, lam: float = 1.0
                     ) -> jax.Array:
    b = base.astype(jnp.float32)
    acc = xs[0].astype(jnp.float32) - b
    for x in xs[1:]:
        acc = acc + (x.astype(jnp.float32) - b)
    return (b + lam * acc).astype(dtype)


MERGES = {"weight_average": _weight_average, "ties": _ties,
          "task_arithmetic": _task_arithmetic}


def merge_leaf(strategy: str, xs: List[jax.Array], base, cfg: Dict
               ) -> jax.Array:
    """Reference merge of one leaf over the contributions that carry it
    (canonical order). `base` is the base leaf, or None when the cell
    has none; a leaf that no contribution carries is the base leaf."""
    if not xs:
        return base
    fn = MERGES[strategy]
    dtype = xs[0].dtype
    if base is None:
        base = jnp.zeros_like(xs[0])
    return jax.block_until_ready(fn(xs, base, dtype, **cfg))


def merged_leaves(strategy: str, trees: Sequence, base, cfg: Dict, *,
                  lower: bool = False) -> Iterator[Tuple[str, jax.Array]]:
    """(key path, reference merge) of every leaf of the model, one leaf
    at a time so that it fits, over `trees` in canonical order. The
    model's leaves are the base's, or with no base the union of the
    contributions'. With `lower` every input is first put through
    `quantize`: the control."""
    by_path = [dict(leaves_with_paths(t)) for t in trees]
    base_by = dict(leaves_with_paths(base)) if base is not None else {}
    paths = sorted(set(base_by).union(*by_path))
    for path in paths:
        xs = [d[path] for d in by_path if path in d]
        b = base_by.get(path)
        if lower:
            xs = [quantize(x) for x in xs]
            b = None if b is None else quantize(b)
        yield path, merge_leaf(strategy, xs, b, cfg)


def quantize(x: jax.Array) -> jax.Array:
    """int8 copy of a leaf (symmetric, one scale per leaf), returned in
    the leaf's dtype: what a merge that held its inputs in int8 sees."""
    f = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(f)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(f / scale), -127, 127).astype(jnp.int8)
    return (q.astype(jnp.float32) * scale).astype(x.dtype)


@jax.jit
def leaf_gap(got: jax.Array, want: jax.Array) -> jax.Array:
    """Widest gap of one leaf: max |got - want| over max(|want|, floor),
    where the floor is 2^-8 of the leaf's largest |want| (half a bf16
    step at that magnitude), so entries near zero are judged on the
    leaf's own scale."""
    g = got.astype(jnp.float32)
    w = want.astype(jnp.float32)
    floor = jnp.maximum(jnp.max(jnp.abs(w)) * 2.0 ** -8, 1e-30)
    return jnp.max(jnp.abs(g - w) / jnp.maximum(jnp.abs(w), floor))
