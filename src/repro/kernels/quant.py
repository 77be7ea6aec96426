"""int8 merge-on-arrival kernel: dequantize inside the tile, fp32 accumulate.

Symmetric int8 wire frames (`core.compression.CompressedLeaf`: q int8,
fp32 scale, zero-point identically 0) used to take a full dequantize
round trip before merging — k x P fp32 tensors written to and re-read
from HBM just to feed the n-ary accumulator. This kernel consumes the
int8 payload directly: each grid step loads a (k, BLOCK) int8 tile
(4x less HBM traffic than fp32), the per-(leaf, contribution) scales
from its leaf's row of an [L, k, 1] table (picked through the scalar-
prefetched block -> leaf map, as in `kernels.histogram`), dequantizes in
VMEM, and accumulates in fp32. The dequantized fp32 copies never exist in HBM.

Byte-identity contract: `q.astype(fp32) * scale` inside the tile is the
exact op `core.compression.decompress_tree` applies, so the kernel
output equals dequantize-then-`nary_accum_ref` bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import leaf_row_spec


def _quant_nary_kernel(leaf_ref, q_ref, base_ref, scale_ref, w_ref,
                       out_ref):
    q = q_ref[...]                          # [k, B] int8
    base = base_ref[...]                    # [1, B] fp32
    scale = scale_ref[...]                  # [k, 1] this block's leaf
    w = w_ref[...]                          # [k, 1] fp32
    x = q.astype(jnp.float32) * scale       # decompress_tree, in-tile
    acc = jnp.sum(w * (x - base), axis=0, keepdims=True)
    out_ref[...] = base + acc


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quant_nary_pallas(q_stacked, base, leaf_id, scales, weights, *,
                      block: int, interpret: bool):
    """q_stacked: [k, Np] int8; base: [1, Np] fp32; leaf_id: [nblocks]
    int32 leaf of each block; scales: [L, k, 1] fp32 per-(leaf,
    contribution) scales; weights: [k, 1] fp32. Returns [1, Np] fp32."""
    k, npad = q_stacked.shape
    return pl.pallas_call(
        _quant_nary_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(npad // block,),
            in_specs=[
                pl.BlockSpec((k, block), lambda i, leaf: (0, i)),
                pl.BlockSpec((1, block), lambda i, leaf: (0, i)),
                leaf_row_spec(k, 1),
                pl.BlockSpec((k, 1), lambda i, leaf: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, block), lambda i, leaf: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, npad), jnp.float32),
        interpret=interpret,
    )(leaf_id, q_stacked, base, scales, weights)
