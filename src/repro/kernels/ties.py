"""Fused TIES merge kernel: trim -> sign-elect -> agreeing mean.

Naive TIES is 5+ elementwise passes over k x p elements (abs, compare,
mask, sign-sum, where, mean) — all memory-bound HBM round trips on TPU.
This kernel fuses the entire pipeline after the (global, sort-based)
trim-threshold computation into a single streaming pass: each grid step
loads one (k, BLOCK) tile of stacked contributions plus the base tile,
and writes one merged tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def ties_tile(x, base, thr):
    """The fused trim -> sign-elect -> agreeing-mean arithmetic on one
    (k, B) tile. Shared by the per-leaf kernel and the flat-batch
    histogram-trim kernel (`kernels.histogram`) so both paths run the
    byte-identical fp32 op sequence."""
    tau = x - base
    mask = (jnp.abs(tau) >= thr).astype(jnp.float32)
    trimmed = tau * mask
    elected = jnp.sign(jnp.sum(trimmed, axis=0, keepdims=True))
    agree = ((jnp.sign(trimmed) == elected) & (trimmed != 0)).astype(
        jnp.float32)
    cnt = jnp.maximum(jnp.sum(agree, axis=0, keepdims=True), 1.0)
    merged = jnp.sum(trimmed * agree, axis=0, keepdims=True) / cnt
    return base + merged


def _ties_kernel(x_ref, base_ref, thr_ref, out_ref):
    x = x_ref[...]                       # [k, B] fp32
    base = base_ref[...]                 # [1, B]
    thr = thr_ref[...]                   # [k, 1]
    out_ref[...] = ties_tile(x, base, thr)


@functools.partial(jax.jit,
                   static_argnames=("block", "interpret"))
def ties_pallas(stacked, base, thresholds, *, block: int,
                interpret: bool):
    """stacked: [k, Np] fp32 (padded); base: [1, Np]; thresholds: [k, 1]."""
    k, npad = stacked.shape
    grid = (npad // block,)
    return pl.pallas_call(
        _ties_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, npad), jnp.float32),
        interpret=interpret,
    )(stacked, base, thresholds)
