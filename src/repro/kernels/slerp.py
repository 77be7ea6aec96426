"""Two-pass SLERP kernel.

Pass 1 (reduction): blocked partial sums of (u.v, u.u, v.v) — one read of
each operand. Pass 2 (elementwise): out = (w1*u/nu + w2*v/nv) * mag with
the trig scalars computed between passes — one more read + one write.
Total: 2 reads/operand vs 4+ for the eager pipeline (normalize, dot,
interpolate, rescale).

Both passes view the [1, Np] operands as [Np / 128, 128] so every tile
is lane-dense; the reduce pass keeps its three running sums as lane
partials in rows 0-2 of one resident (8, 128) output block, and the
combine pass reads its two scalars from SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _reduce_kernel(u_ref, v_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...]                      # [B / 128, 128]
    v = v_ref[...]
    out_ref[0:1, :] += jnp.sum(u * v, axis=0, keepdims=True)
    out_ref[1:2, :] += jnp.sum(u * u, axis=0, keepdims=True)
    out_ref[2:3, :] += jnp.sum(v * v, axis=0, keepdims=True)


def _combine_kernel(s_ref, u_ref, v_ref, out_ref):
    c1 = s_ref[0]                       # w1 * mag / nu
    c2 = s_ref[1]                       # w2 * mag / nv
    out_ref[...] = c1 * u_ref[...] + c2 * v_ref[...]


@functools.partial(jax.jit, static_argnames=("t", "block", "interpret"))
def slerp_pallas(u, v, *, t: float = 0.5, block: int, interpret: bool):
    """u, v: [1, Np] fp32 padded (Np a multiple of `block`, itself a
    multiple of 128). Returns [1, Np]."""
    npad = u.shape[1]
    grid = (npad // block,)
    rows = block // 128
    u2, v2 = u.reshape(-1, 128), v.reshape(-1, 128)
    tile = pl.BlockSpec((rows, 128), lambda i: (i, 0))
    partials = pl.pallas_call(
        _reduce_kernel,
        grid=grid,
        in_specs=[tile, tile],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        interpret=interpret,
    )(u2, v2)
    dot, uu, vv = (jnp.sum(partials[0]), jnp.sum(partials[1]),
                   jnp.sum(partials[2]))
    eps = jnp.float32(1e-12)
    nu, nv = jnp.sqrt(uu) + eps, jnp.sqrt(vv) + eps
    cos = jnp.clip(dot / (nu * nv), -1.0, 1.0)
    omega = jnp.arccos(cos)
    so = jnp.sin(omega)
    w1 = jnp.where(so < 1e-6, 1.0 - t, jnp.sin((1.0 - t) * omega) / so)
    w2 = jnp.where(so < 1e-6, t, jnp.sin(t * omega) / so)
    mag = (1.0 - t) * nu + t * nv
    scalars = jnp.stack([w1 * mag / nu, w2 * mag / nv])
    out = pl.pallas_call(
        _combine_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(u2.shape, jnp.float32),
        interpret=interpret,
    )(scalars, u2, v2)
    return out.reshape(1, npad)
