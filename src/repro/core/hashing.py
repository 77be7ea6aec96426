"""Content hashing for model contributions.

Two tiers:
  * `tensor_digest` / `pytree_digest`: SHA-256 over canonical bytes
    (dtype | shape | row-major data, keys in sorted order). The paper's
    canonical identifier (Assumption 11).
  * `fingerprint2x32`: a jittable, *sharding-invariant* integer fingerprint
    (beyond paper): each element contributes `word * mix(global_index)`
    under exact wrap-around uint32 arithmetic, so partial sums from any
    sharding combine with an integer psum to the identical value. Used as
    the intra-cluster fast path for dedup; SHA-256 remains the canonical
    identity.

`tensor_digest` works on the host in two steps, spanned apart:
`hash.fetch` (the copy of the array to host memory) and `hash.sha256`
(the contiguous copy and the SHA-256). `tree_nbytes` gives the bytes a
digest of a pytree hashes, for its caller's `hash_bytes_total`.
`pytree_leaf_digests` returns the per-leaf digests a content id was
combined from, so the planner can take them instead of hashing the
same leaves again. It hashes the same bytes as `tensor_digest`, but
not one leaf after another: every leaf's copy to the host starts at
once, and the leaves are hashed straight from their host buffers on
`digest_workers` threads of a shared pool (`hashlib` releases the GIL
while it hashes). Those threads open no spans: the tracer keeps one
stack, so the caller's span covers the whole digest.
"""
from __future__ import annotations

import hashlib
import os
import threading
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import span

_MIX_A = np.uint32(2654435761)   # Knuth multiplicative
_MIX_B = np.uint32(0x9E3779B9)
_MIX_C = np.uint32(0x85EBCA6B)
_MIX_D = np.uint32(0xC2B2AE35)


def tensor_digest(arr) -> bytes:
    with span("hash.fetch"):
        a = np.asarray(arr)
    with span("hash.sha256"):
        h = hashlib.sha256()
        h.update(str(a.dtype).encode())
        h.update(b"|")
        h.update(str(a.shape).encode())
        h.update(b"|")
        h.update(np.ascontiguousarray(a).tobytes())
        return h.digest()


def _leaf_digest(leaf) -> bytes:
    """`tensor_digest` of `leaf`, unspanned, hashed from the host
    array's own buffer: a `uint8` view of it holds exactly the bytes
    that `tobytes()` would copy. An array of references (an object
    leaf) has no such view and is copied, as `tensor_digest` does."""
    a = np.asarray(leaf)
    h = hashlib.sha256()
    h.update(str(a.dtype).encode())
    h.update(b"|")
    h.update(str(a.shape).encode())
    h.update(b"|")
    c = np.ascontiguousarray(a)
    h.update(c.tobytes() if c.dtype.hasobject
             else c.reshape(-1).view(np.uint8))
    return h.digest()


def digest_workers(n_leaves: int) -> int:
    """Threads `pytree_leaf_digests` hashes `n_leaves` leaves on: one a
    leaf, up to the host's cores. 1 means inline on the caller."""
    return max(1, min(n_leaves, os.cpu_count() or 1))


_POOL: Optional[ThreadPoolExecutor] = None
_POOL_LOCK = threading.Lock()


def _pool() -> ThreadPoolExecutor:
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            _POOL = ThreadPoolExecutor(max_workers=os.cpu_count() or 1,
                                       thread_name_prefix="leaf-digest")
        return _POOL


def _digest_leaves(leaves: List) -> List[bytes]:
    """`_leaf_digest` of each leaf, in order. Every device-to-host copy
    starts first, largest leaf first; the leaves are then hashed on the
    pool, largest first, so that the last copy to land is what the
    caller waits for. A leaf whose digest raises re-raises here, once
    no task of this call is still running."""
    largest_first = sorted(range(len(leaves)),
                           key=lambda i: -int(getattr(leaves[i], "nbytes",
                                                      0)))
    for i in largest_first:
        start_copy = getattr(leaves[i], "copy_to_host_async", None)
        if start_copy is not None:
            start_copy()
    if digest_workers(len(leaves)) == 1:
        return [_leaf_digest(leaf) for leaf in leaves]
    pool = _pool()
    futures = {i: pool.submit(_leaf_digest, leaves[i])
               for i in largest_first}
    try:
        return [futures[i].result() for i in range(len(leaves))]
    finally:
        for f in futures.values():
            f.cancel()
        wait(futures.values())


def tree_nbytes(tree) -> int:
    """Bytes of a pytree's leaves: what `pytree_digest` hashes (read
    from the arrays' metadata; nothing is copied to the host)."""
    return sum(int(getattr(l, "nbytes", None) or np.asarray(l).nbytes)
               for l in jax.tree_util.tree_leaves(tree))


def pytree_leaf_digests(tree) -> Tuple[bytes, Dict[str, bytes]]:
    """SHA-256 of a parameter pytree together with the `tensor_digest`
    of each leaf, keyed by its `keystr` path: leaves hashed once, on
    `digest_workers` threads, combined in sorted path order. A path
    two leaves share keeps the last one's digest only, so a caller
    reusing the digests checks that the map holds one entry per leaf."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    items = sorted(((jax.tree_util.keystr(p), l) for p, l in flat),
                   key=lambda kv: kv[0])
    digests = _digest_leaves([leaf for _, leaf in items])
    h = hashlib.sha256()
    leaf_digests: Dict[str, bytes] = {}
    for (path, _), digest in zip(items, digests):
        h.update(path.encode())
        h.update(digest)
        leaf_digests[path] = digest
    return h.digest(), leaf_digests


def pytree_digest(tree) -> bytes:
    """SHA-256 of a parameter pytree: leaves hashed, combined in path order."""
    return pytree_leaf_digests(tree)[0]


def hexdigest(tree) -> str:
    return pytree_digest(tree).hex()


def leaf_paths_of(tree) -> Tuple[str, ...]:
    """Canonical sorted `keystr` paths of a pytree's leaves — the leaf
    coverage descriptor of a (possibly partial) contribution."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return tuple(sorted(jax.tree_util.keystr(p) for p, _ in flat))


# ---------------------------------------------------------------------------
# Jittable order-independent fingerprint
# ---------------------------------------------------------------------------


def _words_u32(x: jax.Array) -> jax.Array:
    x = x.reshape(-1)
    if x.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    if x.dtype == jnp.bfloat16:
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    if x.dtype in (jnp.int32, jnp.uint32):
        return x.astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(
        x.astype(jnp.float32), jnp.uint32)


def fingerprint2x32(x: jax.Array) -> jax.Array:
    """Returns uint32[2]; exact, associative-commutative accumulation."""
    w = _words_u32(x)
    i = jax.lax.iota(jnp.uint32, w.shape[0])
    k1 = (i * _MIX_A + _MIX_B) ^ (i >> 7)
    k2 = (i * _MIX_C + _MIX_D) ^ (i << 3)
    lane1 = jnp.sum(w * k1, dtype=jnp.uint32)
    lane2 = jnp.sum((w ^ k2) * _MIX_A, dtype=jnp.uint32)
    return jnp.stack([lane1, lane2])


@jax.jit
def tree_fingerprint(tree) -> jax.Array:
    """uint32[2] fingerprint of a whole pytree (leaf order = path order)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    acc = jnp.zeros((2,), jnp.uint32)
    for idx, (path, leaf) in enumerate(
            sorted(flat, key=lambda kv: jax.tree_util.keystr(kv[0]))):
        fp = fingerprint2x32(leaf)
        rot = jnp.uint32(idx * 0x9E3779B9 + 1)
        acc = acc + fp * rot
    return acc
