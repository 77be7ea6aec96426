"""Jit'd public wrappers over the Pallas merge kernels.

These operate on contribution pytrees (per-leaf), handle flatten/pad/
unpad, compute the global pieces that need a reduction epilogue (SLERP
scalars, histogram trim thresholds), and dispatch to the kernels.

Defaults come from `kernels.config.kernel_env` — block size, interpret
mode (backend probed once, `REPRO_KERNEL_INTERPRET` overrides), and
histogram bins — instead of per-call backend probing.

The `*_batch_merge` entry points are the merge engine's kernel-frontier
dispatch: many same-dtype leaves, each zero-padded to a block multiple
and concatenated into one [k, N] flat batch so every (k, BLOCK) tile
belongs to exactly one leaf, merged in one kernel launch (3 launches
for histogram TIES) per batch instead of one per tensor.
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.common import pad_flat, pad_stacked, pad_stacked_raw
from repro.kernels.config import kernel_env
from repro.kernels.dare import dare_block_pallas, dare_pallas, seed_bits
from repro.kernels.histogram import batch_layout, ties_hist_batch
from repro.kernels.nary_accum import nary_accum_pallas
from repro.kernels.quant import quant_nary_pallas
from repro.kernels.slerp import slerp_pallas
from repro.kernels.ties import ties_pallas


def _defaults(block: Optional[int],
              interpret: Optional[bool]) -> Tuple[int, bool]:
    if block is None:
        block = kernel_env.block
    if interpret is None:
        interpret = kernel_env.resolve_interpret()
    return block, interpret


def _per_leaf(contribs: List[Any], base: Optional[Any]):
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(list(xs)), *contribs)
    if base is None:
        base = jax.tree_util.tree_map(jnp.zeros_like, contribs[0])
    ls, treedef = jax.tree_util.tree_flatten(stacked)
    lb = treedef.flatten_up_to(base)
    return ls, lb, treedef


def _unpad(out, n, shape, dtype):
    dt = jnp.dtype(dtype)
    if not jnp.issubdtype(dt, jnp.floating):
        # fp32 kernel output silently truncates toward zero under an
        # integer astype — surface the programming error instead
        raise TypeError(
            f"kernel output cannot be cast to non-float dtype {dt.name}: "
            "merge kernels accumulate in fp32; integer leaves must take "
            "the eager path")
    return out.reshape(-1)[:n].reshape(shape).astype(dt)


# ------------------------------------------------------------ flat batch --


def _flat_batch(leaves: Sequence[jax.Array], base_leaves: Sequence[jax.Array],
                block: int, *, raw: bool = False):
    """Pad each leaf to a block multiple and concatenate.

    `leaves[j]`: [k, n_j] (same k); `base_leaves[j]`: [n_j]. Returns
    (stacked [k, Np], base [1, Np], lengths, leaf_id, first, offsets)
    where `leaf_id`/`first` are `histogram.batch_layout`'s block -> leaf
    map and `offsets[j]` is leaf j's padded start column.
    """
    pad_s = pad_stacked_raw if raw else pad_stacked
    parts, bparts, lengths, offsets = [], [], [], []
    off = 0
    for s, b in zip(leaves, base_leaves):
        sp, n = pad_s(s, block)
        bp, _ = pad_flat(b, block)
        parts.append(sp)
        bparts.append(bp)
        lengths.append(int(n))
        offsets.append(off)
        off += sp.shape[1]
    stacked = jnp.concatenate(parts, axis=1)
    base = jnp.concatenate(bparts)[None, :]
    leaf_id, first, total = batch_layout(lengths, block)
    assert total == stacked.shape[1]
    return stacked, base, lengths, leaf_id, first, offsets


def _split_flat(out, lengths: List[int], offsets: List[int],
                block: int) -> List[jax.Array]:
    flat = out.reshape(-1)
    return [flat[off:off + n] for off, n in zip(offsets, lengths)]


def ties_batch_merge(leaves: Sequence[jax.Array],
                     base_leaves: Sequence[jax.Array],
                     trim: float = 0.2, *, bins: Optional[int] = None,
                     block: Optional[int] = None,
                     interpret: Optional[bool] = None) -> List[jax.Array]:
    """Histogram-trim TIES over many leaves in one flat-batch dispatch.

    3 kernel launches (amax, histogram, fused merge) for the whole
    batch; byte-identical per leaf to `ref.ties_hist_ref`. Returns
    unpadded fp32 1-D arrays, one per leaf.
    """
    block, interpret = _defaults(block, interpret)
    bins = kernel_env.hist_bins if bins is None else bins
    stacked, base, lengths, leaf_id, first, offsets = _flat_batch(
        leaves, base_leaves, block)
    out = ties_hist_batch(
        stacked, base, leaf_id, first,
        jnp.asarray(lengths, jnp.int32),
        trim=trim, bins=bins, block=block, interpret=interpret)
    return _split_flat(out, lengths, offsets, block)


def dare_batch_merge(leaves: Sequence[jax.Array],
                     base_leaves: Sequence[jax.Array],
                     seeds: Sequence[int], p: float = 0.5, *,
                     block: Optional[int] = None,
                     interpret: Optional[bool] = None) -> List[jax.Array]:
    """Flat-batch DARE: one launch for many leaves, byte-identical to
    per-leaf `dare_pallas` with the same per-leaf seed.

    `seeds[j]` is leaf j's uint32 RNG seed (the engine threads the
    plan's global leaf index into it so replicas agree).
    """
    block, interpret = _defaults(block, interpret)
    stacked, base, lengths, leaf_id, first, offsets = _flat_batch(
        leaves, base_leaves, block)
    # per leaf: (seed bits, padded length, first block)
    meta = jnp.concatenate([
        jnp.concatenate([seed_bits(s), jnp.asarray(
            [-(-ln // block) * block, off // block], jnp.int32)])
        for s, ln, off in zip(seeds, lengths, offsets)])
    out = dare_block_pallas(stacked, base, leaf_id, meta, p=p,
                            block=block, interpret=interpret)
    return _split_flat(out, lengths, offsets, block)


def quant_batch_merge(q_leaves: Sequence[jax.Array],
                      scales: Sequence[jax.Array],
                      base_leaves: Sequence[jax.Array],
                      weights, *, block: Optional[int] = None,
                      interpret: Optional[bool] = None) -> List[jax.Array]:
    """int8 merge-on-arrival over many leaves in one launch.

    `q_leaves[j]`: [k, n_j] int8 wire payloads; `scales[j]`: [k] fp32
    per-contribution dequant scales for leaf j; `weights`: [k] n-ary
    scalars. Dequantization happens inside the tile — no fp32 copy of
    the stacked batch ever reaches HBM. Byte-identical per leaf to
    `ref.quant_nary_ref`.
    """
    block, interpret = _defaults(block, interpret)
    stacked, base, lengths, leaf_id, first, offsets = _flat_batch(
        q_leaves, base_leaves, block, raw=True)
    scale_rows = jnp.stack([jnp.asarray(s, jnp.float32).reshape(-1, 1)
                            for s in scales])              # [L, k, 1]
    w = jnp.asarray(weights, jnp.float32).reshape(-1, 1)
    out = quant_nary_pallas(stacked, base, leaf_id, scale_rows, w,
                            block=block, interpret=interpret)
    return _split_flat(out, lengths, offsets, block)


# ------------------------------------------------------------- per-leaf --


def ties_merge(contribs, base=None, trim: float = 0.2, *,
               trim_method: str = "histogram",
               block: Optional[int] = None,
               interpret: Optional[bool] = None):
    """Fused TIES. `trim_method="histogram"` (default) resolves the trim
    threshold with the sort-free two-pass histogram kernel — the same
    path the engine's flat-batch dispatch uses; `"quantile"` keeps the
    exact sort-based threshold (one `jnp.quantile` per leaf, blocks
    batching)."""
    block, interpret = _defaults(block, interpret)
    ls, lb, treedef = _per_leaf(contribs, base)
    outs = []
    if trim_method == "histogram":
        flats = [s.reshape(s.shape[0], -1) for s in ls]
        merged = ties_batch_merge(
            flats, [b.reshape(-1) for b in lb], trim,
            block=block, interpret=interpret)
        for m, s, b in zip(merged, ls, lb):
            outs.append(m.reshape(b.shape).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, outs)
    if trim_method != "quantile":
        raise ValueError(f"unknown trim_method {trim_method!r}")
    for s, b in zip(ls, lb):
        sp, n = pad_stacked(s, block)
        bp, _ = pad_flat(b, block)
        # global (sort-based) trim thresholds, fp32, on the unpadded region
        # (must match the kernel's fp32 tau exactly at the boundary)
        thr = jnp.quantile(
            jnp.abs(sp[:, :n] - bp[None, :n]),
            trim, axis=1).astype(jnp.float32).reshape(-1, 1)
        out = ties_pallas(sp, bp[None, :], thr, block=block,
                          interpret=interpret)
        outs.append(_unpad(out, n, b.shape, s.dtype))
    return jax.tree_util.tree_unflatten(treedef, outs)


def dare_merge(contribs, base=None, seed: int = 0, p: float = 0.5, *,
               block: Optional[int] = None,
               interpret: Optional[bool] = None):
    block, interpret = _defaults(block, interpret)
    ls, lb, treedef = _per_leaf(contribs, base)
    outs = []
    for i, (s, b) in enumerate(zip(ls, lb)):
        sp, n = pad_stacked(s, block)
        bp, _ = pad_flat(b, block)
        sd = jnp.asarray([[seed + i]], jnp.uint32)
        out = dare_pallas(sp, bp[None, :], sd, p=p, block=block,
                          interpret=interpret)
        outs.append(_unpad(out, n, b.shape, s.dtype))
    return jax.tree_util.tree_unflatten(treedef, outs)


def nary_flat_merge(stacked_flat, base_flat, weights, *,
                    block: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    preserve_dtype: bool = False):
    """One fused nary_accum dispatch over an already-flattened batch.

    `stacked_flat`: [k, N] — many same-dtype leaves' slices concatenated
    along the element axis (the merge engine's batched dispatch);
    `base_flat`: [N]; `weights`: [k] scalars. Returns fp32 [N]
    (out = base + sum_i w_i (x_i - base)), one HBM pass for the whole
    batch instead of one kernel launch per leaf.

    `preserve_dtype=True` streams sub-fp32 inputs (bf16/fp16) through
    HBM in their own dtype and upcasts inside the tile — half the read
    traffic, identical fp32 result (the kernel widens before any
    arithmetic, exactly as the eager stack-then-cast would).
    """
    block, interpret = _defaults(block, interpret)
    pad_s = pad_stacked_raw if preserve_dtype else pad_stacked
    sp, n = pad_s(stacked_flat, block)
    bp, _ = pad_flat(base_flat, block)
    w = jnp.asarray(weights, jnp.float32).reshape(-1, 1)
    out = nary_accum_pallas(sp, bp[None, :], w, block=block,
                            interpret=interpret)
    return out.reshape(-1)[:n]


def weighted_merge(contribs, weights, base=None, *,
                   block: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """out = base + sum_i w_i (x_i - base). weights: [k] scalars."""
    block, interpret = _defaults(block, interpret)
    ls, lb, treedef = _per_leaf(contribs, base)
    w = jnp.asarray(weights, jnp.float32).reshape(-1, 1)
    outs = []
    for s, b in zip(ls, lb):
        sp, n = pad_stacked(s, block)
        bp, _ = pad_flat(b, block)
        out = nary_accum_pallas(sp, bp[None, :], w, block=block,
                                interpret=interpret)
        outs.append(_unpad(out, n, b.shape, s.dtype))
    return jax.tree_util.tree_unflatten(treedef, outs)


def weight_average_merge(contribs, base=None, **kw):
    k = len(contribs)
    zero = jax.tree_util.tree_map(jnp.zeros_like, contribs[0])
    return weighted_merge(contribs, jnp.full((k,), 1.0 / k), zero, **kw)


def task_arithmetic_merge(contribs, base, lam: float = 1.0, **kw):
    k = len(contribs)
    return weighted_merge(contribs, jnp.full((k,), lam), base, **kw)


def slerp_merge(a, b_tree, t: float = 0.5, *, block: Optional[int] = None,
                interpret: Optional[bool] = None):
    block, interpret = _defaults(block, interpret)
    la, treedef = jax.tree_util.tree_flatten(a)
    lb = treedef.flatten_up_to(b_tree)
    outs = []
    for u, v in zip(la, lb):
        up, n = pad_flat(u, block)
        vp, _ = pad_flat(v, block)
        out = slerp_pallas(up[None, :], vp[None, :], t=t, block=block,
                           interpret=interpret)
        outs.append(_unpad(out, n, u.shape, u.dtype))
    return jax.tree_util.tree_unflatten(treedef, outs)
