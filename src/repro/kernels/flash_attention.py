"""Flash attention (online-softmax) Pallas kernel — the §Perf "next lever".

The baseline chunked attention materializes fp32 logits/probs tiles of
q_chunk x S in HBM; the roofline analysis (EXPERIMENTS.md §Roofline) shows
this softmax traffic dominates the memory term of every train/prefill
cell. This kernel keeps the running max / normalizer / accumulator in
VMEM scratch across the KV-block grid dimension, so per-element HBM
traffic drops to reads of Q,K,V + one write of O.

Canonical Pallas pattern: grid = (B*H, Sq/BQ, Sk/BK) with the KV dimension
innermost ('arbitrary' semantics on TPU); @pl.when guards initialize and
finalize the scratch. GQA is handled in the K/V index maps (kv head =
h // group). Causal masking is position-based per tile.

NOTE: intentionally NOT wired into the dry-run model — a custom call would
hide FLOPs/bytes from the HLO-derived roofline (DESIGN.md §6). Validated
with interpret=True against the pure-jnp oracle; the traffic win is
reported analytically in benchmarks/bench_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  n_k: int):
    i_k = pl.program_id(2)

    @pl.when(i_k == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                 # [BQ, D]
    k = k_ref[0].astype(jnp.float32)                 # [BK, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        i_q = pl.program_id(1)
        q_pos = i_q * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        k_pos = i_k * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = (acc_scr[...] * alpha[:, None]
                    + jax.lax.dot_general(
                        p, v_ref[0].astype(jnp.float32),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(i_k == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret",
                              "scale"))
def flash_attention(q, k, v, *, causal: bool = True, scale: float = 0.0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool):
    """q: [B, Sq, H, D]; k/v: [B, Sk, HK, D] (H a multiple of HK).

    Returns [B, Sq, H, D]. Sq/Sk padded internally to block multiples.
    """
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    assert h % hk == 0
    group = h // hk
    if scale <= 0.0:
        scale = d ** -0.5

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.concatenate(
            [q, jnp.zeros((b, pad_q, h, d), q.dtype)], axis=1)
    if pad_k:
        # pad keys at -inf effect: zeros are masked by causality for the
        # padded q rows; for non-causal, mask via large negative k? Use
        # explicit validity through causal positions only; for non-causal
        # pad keys contribute exp(-inf)=0 via the position mask below.
        k = jnp.concatenate(
            [k, jnp.zeros((b, pad_k, hk, d), k.dtype)], axis=1)
        v = jnp.concatenate(
            [v, jnp.zeros((b, pad_k, hk, d), v.dtype)], axis=1)

    sq_p, sk_p = sq + pad_q, sk + pad_k
    # flatten heads into the leading grid dimension
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq_p, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hk, sk_p, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hk, sk_p, d)

    n_q = sq_p // block_q
    n_k = sk_p // block_k
    grid = (b * h, n_q, n_k)

    def q_map(ibh, iq, ik):
        return (ibh, iq, 0)

    def kv_map(ibh, iq, ik):
        bi = ibh // h
        kv = (ibh % h) // group
        return (bi * hk + kv, ik, 0)

    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k=n_k)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, h, sq_p, d).transpose(0, 2, 1, 3)
    return out[:, :sq]
