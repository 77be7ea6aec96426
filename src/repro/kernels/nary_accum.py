"""Fused n-ary weighted accumulation kernel.

out = base + sum_i w_i * (x_i - base)

Covers the whole linear family in one HBM pass with fp32 accumulation:
weight averaging (w=1/k, base=0), linear interpolation, task arithmetic
(w=lambda), negative merge (w=-lambda/k), DAM / AdaMerging (per-
contribution scalar weights computed outside from norms/variances).

The merge engine's batched executor (`core/engine`) concatenates many
same-dtype leaves into a single [k, N] flat batch and dispatches it
here once via `ops.nary_flat_merge` — one kernel launch per batch
instead of one per tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _nary_kernel(x_ref, base_ref, w_ref, out_ref):
    # in-kernel upcast: bf16 (and other sub-fp32) inputs stream through
    # HBM in their wire dtype and widen in VMEM — fp32 is a no-op cast
    x = x_ref[...].astype(jnp.float32)    # [k, B]
    base = base_ref[...]                  # [1, B]
    w = w_ref[...]                        # [k, 1]
    acc = jnp.sum(w * (x - base), axis=0, keepdims=True)
    out_ref[...] = base + acc


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def nary_accum_pallas(stacked, base, weights, *, block: int,
                      interpret: bool):
    """stacked: [k, Np]; base: [1, Np]; weights: [k, 1] fp32."""
    k, npad = stacked.shape
    grid = (npad // block,)
    return pl.pallas_call(
        _nary_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, npad), jnp.float32),
        interpret=interpret,
    )(stacked, base, weights)
