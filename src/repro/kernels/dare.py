"""Fused DARE kernel: in-kernel counter-based RNG -> mask -> rescale -> mean.

The Bernoulli mask is derived from the Merkle seed and the *global*
element index via a stateless uint32 hash, entirely inside the kernel —
the k x p mask never exists in HBM (vs. the eager pipeline which
materializes the random tensor, the mask, and the rescaled taus). One
streaming pass: read (k, BLOCK) + base tile, write merged tile.

The kernel is layout-driven so the per-leaf path and the engine's flat-
batch dispatch share one body: each grid step looks up its leaf in the
scalar-prefetched `leaf_id` table (SMEM), reads that leaf's (seed, padded
length, first block) row, and reconstructs the same `row * npad + col`
global index the per-leaf launch would have used. Because the hash is
exact uint32 arithmetic, flat-batch output is byte-identical to per-leaf
dispatch by construction — a batch block at offset `start` inside its
leaf draws exactly the mask the standalone launch drew at that offset.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import hash_uniform


def _dare_kernel(leaf_ref, meta_ref, x_ref, base_ref, out_ref, *, p: float,
                 block: int):
    i = pl.program_id(0)
    leaf = leaf_ref[i]
    seed = meta_ref[3 * leaf]
    npad = meta_ref[3 * leaf + 1]
    start = (i - meta_ref[3 * leaf + 2]) * block
    x = x_ref[...]                          # [k, B]
    base = base_ref[...]                    # [1, B]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + start
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    # int32 arithmetic wraps exactly like uint32: same index bits
    idx = jax.lax.bitcast_convert_type(row * npad + col, jnp.uint32)
    seed_u = jax.lax.bitcast_convert_type(
        jnp.full((1, 1), seed, jnp.int32), jnp.uint32)
    u = hash_uniform(idx, seed_u)
    keep = (u >= jnp.float32(p)).astype(jnp.float32)
    tau = (x - base) * keep * jnp.float32(1.0 / (1.0 - p))
    out_ref[...] = base + jnp.mean(tau, axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("p", "block", "interpret"))
def dare_block_pallas(stacked, base, leaf_id, leaf_meta, *, p: float,
                      block: int, interpret: bool):
    """Layout-driven DARE: stacked [k, Np] fp32; base [1, Np]; leaf_id
    [nblocks] int32 leaf of each block; leaf_meta [3 * L] int32 rows of
    (seed bits, leaf padded length, first block) per leaf."""
    k, npad = stacked.shape
    kern = functools.partial(_dare_kernel, p=p, block=block)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(npad // block,),
            in_specs=[
                pl.BlockSpec((k, block), lambda i, *_: (0, i)),
                pl.BlockSpec((1, block), lambda i, *_: (0, i)),
            ],
            out_specs=pl.BlockSpec((1, block), lambda i, *_: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, npad), jnp.float32),
        interpret=interpret,
    )(leaf_id, leaf_meta, stacked, base)


def seed_bits(seed) -> jax.Array:
    """A uint32 seed as the int32 bits the kernel's SMEM table holds."""
    return jax.lax.bitcast_convert_type(
        jnp.asarray(seed, jnp.uint32).reshape(-1)[:1], jnp.int32)


def dare_pallas(stacked, base, seed, *, p: float = 0.5, block: int,
                interpret: bool):
    """stacked: [k, Np] fp32; base: [1, Np]; seed: uint32 [1,1]."""
    npad = stacked.shape[1]
    leaf_id = jnp.zeros((npad // block,), jnp.int32)
    meta = jnp.concatenate(
        [seed_bits(seed), jnp.asarray([npad, 0], jnp.int32)])
    return dare_block_pallas(stacked, base, leaf_id, meta, p=p,
                             block=block, interpret=interpret)
