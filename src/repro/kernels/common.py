"""Shared kernel utilities: padding/blocking and the counter-based RNG.

TPU tiling: merge kernels stream [k, N] stacked contributions through
VMEM in (k, BLOCK) tiles, BLOCK a multiple of 1024 (8 sublanes x 128
lanes), one HBM read per contribution element and one write per output
element — the whole point of fusing the merge pipelines (DESIGN.md §6).

The RNG is a stateless 3-round xorshift-multiply hash over the global
element index and the Merkle-derived seed: exact uint32 arithmetic, so
kernel and jnp reference produce bit-identical masks on every replica
(paper Assumption 10).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def pad_flat(x: jax.Array, block: int) -> Tuple[jax.Array, int]:
    """Flatten to 1-D fp32 and zero-pad to a multiple of `block`."""
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    rem = (-n) % block
    if rem:
        flat = jnp.concatenate([flat, jnp.zeros((rem,), jnp.float32)])
    return flat, n


def pad_stacked(s: jax.Array, block: int) -> Tuple[jax.Array, int]:
    """[k, ...] -> [k, Np] fp32 padded."""
    k = s.shape[0]
    flat = s.reshape(k, -1).astype(jnp.float32)
    n = flat.shape[1]
    rem = (-n) % block
    if rem:
        flat = jnp.concatenate(
            [flat, jnp.zeros((k, rem), jnp.float32)], axis=1)
    return flat, n


def pad_stacked_raw(s: jax.Array, block: int) -> Tuple[jax.Array, int]:
    """[k, ...] -> [k, Np] zero-padded, dtype PRESERVED.

    The quantized / bf16 merge-on-arrival kernels upcast inside the
    (k, BLOCK) tile; padding in the wire dtype keeps the fp32 copies of
    the stacked batch out of HBM entirely (the point of those kernels).
    """
    k = s.shape[0]
    flat = s.reshape(k, -1)
    n = flat.shape[1]
    rem = (-n) % block
    if rem:
        flat = jnp.concatenate(
            [flat, jnp.zeros((k, rem), flat.dtype)], axis=1)
    return flat, n


def leaf_row_spec(k: int, last: int) -> pl.BlockSpec:
    """The grid step's row of a per-leaf [L, k, last] table in a flat
    batch, picked through the block -> leaf map that is the first
    scalar-prefetch operand. Consecutive blocks of one leaf map to one
    row, so it is fetched (or written back) once per leaf."""
    return pl.BlockSpec((None, k, last), lambda i, leaf, *_: (leaf[i], 0, 0))


def hash_uniform(idx: jax.Array, seed) -> jax.Array:
    """Deterministic uniform(0,1) floats from uint32 element indices.

    Pure uint32 ops — identical inside Pallas kernels and in jnp refs.
    """
    h = idx.astype(jnp.uint32) * jnp.uint32(2654435761)
    h = h ^ jnp.asarray(seed, jnp.uint32)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    # through int32: Mosaic has no uint32 -> float32 cast, and the value
    # is below 2^24, so both casts are exact
    return (h >> 8).astype(jnp.int32).astype(jnp.float32) * \
        jnp.float32(1.0 / (1 << 24))
