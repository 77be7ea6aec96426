"""Pure-jnp oracles mirroring each kernel's exact computation order.

These are the correctness references for the shape/dtype sweep tests
(kernels validated with interpret=True on CPU; TPU is the target). The
DARE oracle reuses the identical uint32 hash, so masks match bitwise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import hash_uniform


def ties_ref(stacked, base, thresholds):
    tau = stacked - base
    mask = (jnp.abs(tau) >= thresholds).astype(jnp.float32)
    trimmed = tau * mask
    elected = jnp.sign(jnp.sum(trimmed, axis=0, keepdims=True))
    agree = ((jnp.sign(trimmed) == elected) & (trimmed != 0)).astype(
        jnp.float32)
    cnt = jnp.maximum(jnp.sum(agree, axis=0, keepdims=True), 1.0)
    merged = jnp.sum(trimmed * agree, axis=0, keepdims=True) / cnt
    return base + merged


def dare_ref(stacked, base, seed, p=0.5):
    k, npad = stacked.shape
    row = jax.lax.broadcasted_iota(jnp.uint32, (k, npad), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (k, npad), 1)
    idx = row * jnp.uint32(npad) + col
    u = hash_uniform(idx, seed.reshape(())[()] if hasattr(seed, "reshape")
                     else seed)
    keep = (u >= jnp.float32(p)).astype(jnp.float32)
    tau = (stacked - base) * keep * jnp.float32(1.0 / (1.0 - p))
    return base + jnp.mean(tau, axis=0, keepdims=True)


def nary_accum_ref(stacked, base, weights):
    return base + jnp.sum(weights * (stacked - base), axis=0, keepdims=True)


def hist_threshold_ref(stacked, base, trim=0.2, bins=512):
    """Per-contribution trim thresholds, `strategies.catalog
    ._hist_quantile` verbatim (same op order, fp32). Exact regardless
    of layout: the max is associative and the counts are integers in
    fp32, so the flat-batch kernel's per-block passes must reproduce
    these bits."""
    tau = stacked - base                                  # [k, n] fp32
    a = jnp.abs(tau)
    amax = jnp.max(a, axis=1, keepdims=True) + 1e-12
    idx = jnp.clip((a / amax * bins).astype(jnp.int32), 0, bins - 1)
    counts = jax.vmap(
        lambda r: jnp.zeros((bins,), jnp.float32).at[r].add(1.0))(idx)
    cdf = jnp.cumsum(counts, axis=1) / jnp.float32(a.shape[1])
    bucket = jnp.argmax(cdf >= trim, axis=1)              # first crossing
    return (bucket[:, None].astype(jnp.float32) / bins) * amax


def ties_hist_ref(stacked, base, trim=0.2, bins=512):
    """Per-leaf eager oracle for histogram-trim TIES:
    `hist_threshold_ref` then `ties_ref`.

    Byte-identity caveat: XLA CPU's axis-0 reduction inside a jitted
    computation (which is how the interpret-mode kernel body runs) can
    differ by an ulp from the op-by-op one (observed at k=16), so
    bitwise comparisons against the kernel should evaluate the MERGE
    half jitted, on the same block-padded layout the kernel sees —
    thresholds eagerly from the unpadded row (exact either way),
    `jax.jit(ties_ref)` on the padded stack."""
    return ties_ref(stacked, base,
                    hist_threshold_ref(stacked, base, trim, bins))


def quant_nary_ref(q_stacked, scales, base, weights):
    """Dequantize-then-merge oracle: `decompress_tree`'s exact op
    (q.astype(fp32) * scale) followed by `nary_accum_ref`."""
    x = q_stacked.astype(jnp.float32) * scales.reshape(-1, 1)
    return nary_accum_ref(x, base, weights)


def slerp_ref(u, v, t=0.5):
    eps = jnp.float32(1e-12)
    dot = jnp.sum(u * v)
    nu = jnp.sqrt(jnp.sum(u * u)) + eps
    nv = jnp.sqrt(jnp.sum(v * v)) + eps
    cos = jnp.clip(dot / (nu * nv), -1.0, 1.0)
    omega = jnp.arccos(cos)
    so = jnp.sin(omega)
    w1 = jnp.where(so < 1e-6, 1.0 - t, jnp.sin((1.0 - t) * omega) / so)
    w2 = jnp.where(so < 1e-6, t, jnp.sin(t * omega) / so)
    mag = (1.0 - t) * nu + t * nv
    return (w1 * mag / nu) * u + (w2 * mag / nv) * v
