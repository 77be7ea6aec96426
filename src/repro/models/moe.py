"""Token-choice top-k Mixture-of-Experts with two dispatch backends.

`einsum`  — GShard-style one-hot dispatch/combine einsums (baseline; the
            dispatch matmul burns O(G*s*E*C*D) FLOPs on a one-hot operand).
`gather`  — index-based dispatch: positions-in-expert via a cumsum over the
            group, token ids scattered into an [E, C] table (capacity drop),
            expert inputs gathered, outputs gathered back per assignment.
            No sort, no one-hot matmul; FLOPs = router + expert FFN only.
            This is the §Perf-optimized path (see EXPERIMENTS.md).

Sharding: tokens are grouped [G, s, ...] with G on the data axes; expert
tensors [E, ...] carry 'ep' (model axis). The g-sharded -> e-sharded
constraint between dispatch and expert compute is where GSPMD inserts the
MoE all-to-all.

Expert-parallel share (`MoEConfig.expert_start` / `experts_held`): the
router keeps all `num_experts` outputs and every token is routed over
all of them, but the expert stacks hold only this share's experts, and
only assignments to those are dispatched. Positions within an expert
and its capacity are those of the uncut layer, so summing every share's
routed output gives the uncut layer's. Nothing stands in for the
experts held elsewhere; the shared experts are every share's own.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import mlp, mlp_def
from repro.models.schema import PDef


def moe_def(cfg: ModelConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    f = m.d_ff_expert
    e = m.held[1]
    scale = 0.02
    p = {
        "router": PDef((d, m.num_experts), (None, None), scale=scale),
        "experts": {
            "w_gate": PDef((e, d, f), ("ep", "fsdp", None), scale=scale),
            "w_up": PDef((e, d, f), ("ep", "fsdp", None), scale=scale),
            "w_down": PDef((e, f, d), ("ep", None, "fsdp"), scale=scale),
        },
    }
    if m.num_shared_experts:
        p["shared"] = mlp_def(d, m.num_shared_experts * m.d_ff_shared,
                              "swiglu", scale)
    return p


def _router(p, x, m: MoEConfig):
    """x: [G, s, D] -> (gates [G,s,k] fp32, idx [G,s,k] int32, aux loss)."""
    logits = jnp.einsum("gsd,de->gse", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balancing aux loss (Switch-style)
    density = jnp.mean(
        jax.nn.one_hot(idx[..., 0], m.num_experts, dtype=jnp.float32),
        axis=(0, 1))
    mean_probs = jnp.mean(probs, axis=(0, 1))
    aux = m.num_experts * jnp.sum(density * mean_probs)
    return gates, idx, aux


def _capacity(m: MoEConfig, s: int) -> int:
    c = int(m.top_k * s * m.capacity_factor / m.num_experts)
    return max(8, -(-c // 8) * 8)   # round up to 8


def _expert_ffn(experts, xin, variant, compute_dtype):
    """xin: [E or G..., E, C, D] stacked expert inputs -> same with F->D."""
    wg = experts["w_gate"].astype(compute_dtype)
    wu = experts["w_up"].astype(compute_dtype)
    wd = experts["w_down"].astype(compute_dtype)
    g = jnp.einsum("...ecd,edf->...ecf", xin, wg)
    u = jnp.einsum("...ecd,edf->...ecf", xin, wu)
    act = jax.nn.silu(g) if variant == "swiglu" else jax.nn.gelu(g)
    return jnp.einsum("...ecf,efd->...ecd", act * u, wd)


def moe_einsum(p, x, cfg: ModelConfig, compute_dtype):
    """GShard-style masked-einsum dispatch (baseline). x: [G, s, D]."""
    m = cfg.moe
    gdim, s, d = x.shape
    c = _capacity(m, s)
    gates, idx, aux = _router(p, x, m)

    onehot = jax.nn.one_hot(idx, m.num_experts, dtype=jnp.int32)  # [G,s,k,E]
    # position of each assignment within its expert (over s*k, k-major last)
    flat = onehot.reshape(gdim, s * m.top_k, m.num_experts)
    pos = jnp.cumsum(flat, axis=1) - flat                         # [G,sk,E]
    pos = jnp.sum(pos * flat, axis=-1).reshape(gdim, s, m.top_k)  # [G,s,k]
    keep = pos < c
    pos_oh = jax.nn.one_hot(pos, c, dtype=compute_dtype) * keep[..., None]
    start, held = m.held
    onehot = onehot[..., start:start + held]          # the held experts
    # dispatch mask [G, s, E, C] = sum_k onehot_e * onehot_c
    dispatch = jnp.einsum("gske,gskc->gsec",
                          onehot.astype(compute_dtype), pos_oh)
    combine = jnp.einsum("gsk,gske,gskc->gsec",
                         gates.astype(compute_dtype),
                         onehot.astype(compute_dtype), pos_oh)
    xin = jnp.einsum("gsec,gsd->gecd", dispatch, x.astype(compute_dtype))
    xin = _shard_expert(xin)
    yout = _expert_ffn(p["experts"], xin, "swiglu", compute_dtype)
    y = jnp.einsum("gsec,gecd->gsd", combine, yout)
    return y, aux


def moe_gather(p, x, cfg: ModelConfig, compute_dtype):
    """Index-based dispatch (optimized). x: [G, s, D]."""
    m = cfg.moe
    gdim, s, d = x.shape
    c = _capacity(m, s)
    gates, idx, aux = _router(p, x, m)

    onehot_e = jax.nn.one_hot(idx, m.num_experts, dtype=jnp.int32)
    flat = onehot_e.reshape(gdim, s * m.top_k, m.num_experts)
    pos = jnp.cumsum(flat, axis=1) - flat
    pos = jnp.sum(pos * flat, axis=-1).reshape(gdim, s, m.top_k)
    # an assignment is dispatched here when it is within capacity and
    # its expert is held here; idx becomes the index in the held stack
    start, held = m.held
    local = idx - start
    mine = (local >= 0) & (local < held)
    keep = (pos < c) & mine                                      # [G,s,k]
    idx = jnp.where(mine, local, 0)

    token_id = jnp.broadcast_to(jnp.arange(s)[None, :, None],
                                (gdim, s, m.top_k))
    # scatter token ids into the [E, C] dispatch table (drop over capacity)
    def scatter_group(eidx, posg, tidg, keepg):
        tbl = jnp.zeros((held, c), jnp.int32)
        iidx = jnp.stack([eidx.reshape(-1),
                          jnp.where(keepg, posg, c).reshape(-1)], -1)
        return tbl.at[iidx[:, 0], iidx[:, 1]].set(
            tidg.reshape(-1), mode="drop")

    table = jax.vmap(scatter_group)(idx, pos, token_id, keep)   # [G,E,C]
    slot_used = jax.vmap(scatter_group)(
        idx, pos, jnp.ones_like(token_id), keep).astype(bool)

    # gather rows: xin[g, e, c] = x[g, table[g, e, c]]
    xin = jax.vmap(lambda xg, tg: xg[tg.reshape(-1)].reshape(
        held, c, d))(x.astype(compute_dtype), table)
    xin = xin * slot_used[..., None].astype(compute_dtype)
    xin = _shard_expert(xin)
    yout = _expert_ffn(p["experts"], xin, "swiglu", compute_dtype)

    # combine: out[g, s] = sum_k gate * yout[g, e_k, pos_k]
    def combine_group(yg, eg, posg, gateg, keepg):
        rows = yg[eg.reshape(-1), jnp.minimum(posg, c - 1).reshape(-1)]
        rows = rows.reshape(s, m.top_k, d)
        w = (gateg * keepg).astype(compute_dtype)[..., None]
        return jnp.sum(rows * w, axis=1)

    y = jax.vmap(combine_group)(yout, idx, pos, gates, keep)
    return y, aux


def _shard_expert(xin):
    """Hint GSPMD to reshard dispatch output expert-major (the a2a point)."""
    from repro.sharding.policy import expert_activation_constraint
    return expert_activation_constraint(xin)


def moe_block(p, x, cfg: ModelConfig, compute_dtype, impl: str = "gather"):
    """x: [B, S, D] -> (y, aux). Groups = batch rows (data-sharded)."""
    m = cfg.moe
    b, s, d = x.shape
    fn = moe_einsum if impl == "einsum" else moe_gather
    y, aux = fn(p, x, cfg, compute_dtype)
    if m.num_shared_experts:
        y = y + mlp(p["shared"], x, "swiglu", compute_dtype)
    return y, aux
