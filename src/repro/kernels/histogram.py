"""Two-pass histogram trim-quantile + flat-batch TIES merge kernels.

The per-leaf `ops.ties_merge` path computed its trim threshold with a
sort (`jnp.quantile`) — a global operation that blocks batching: every
leaf needed its own sort over k x p elements before the fused merge
kernel could launch, so TIES never joined the engine's one-launch-per-
batch flat dispatch. This module replaces the sort with the catalog's
histogram trim (`strategies.catalog._hist_quantile` math, bit-for-bit):

  pass A  max|tau| per leaf, accumulated over the leaf's blocks (exact:
          max is associative, so blockwise = global bitwise)
  pass B  |tau| histograms per leaf, accumulated over its blocks
          (exact: integer counts in fp32, order-free below 2^24 per
          bucket)
  resolve cdf/argmax threshold per (leaf, contribution) — O(L*k*bins)
          scalars, done in plain jnp outside the kernels
  pass C  fused trim/sign-elect/agreeing-mean merge (`ties.ties_tile`)
          with per-leaf thresholds

Batch layout: each leaf is zero-padded to a multiple of BLOCK *before*
concatenation, so every (k, BLOCK) tile belongs to exactly one leaf.
The block -> leaf map (`leaf_id`, one int32 per block) and the per-leaf
first block and length ride in SMEM as scalar-prefetch operands; the
per-leaf [k, ...] tables (amax, counts, thresholds) are [L, k, ...]
arrays whose row the BlockSpec index map picks through `leaf_id`. The
TPU lowering accepts these blocks because their last two dims are the
array's own, and no per-block metadata array exists in HBM. SMEM cost:
4 bytes per block (48 KiB for the 12,288 blocks of a 25 M-element leaf
at BLOCK=2048, of the 1 MiB a v5e core has). Three streaming passes
over the stacked bytes total,
versus the eager pipeline's one-pass-per-op chain (see
`benchmarks/bench_kernels.py` for the exact accounting the CI gate
enforces).

Byte-identity contract: for every leaf, the flat-batch output equals
`kernels.ref.ties_hist_ref` (the per-leaf eager oracle) bitwise, for
leaves up to 2^24 elements per histogram bucket (beyond that the eager
fp32 scatter-add itself saturates).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import leaf_row_spec
from repro.kernels.ties import ties_tile

# VMEM budget for the one-hot expansion inside the histogram kernel:
# the [bins, CHUNK] fp32 intermediate of one contribution row is the
# largest value the pass materializes; keep it under ~1 MiB by
# shrinking the column chunk.
_ONEHOT_VMEM_BYTES = 1024 * 1024


def _hist_chunk(bins: int, block: int) -> int:
    chunk = block
    while chunk > 128 and chunk * bins * 4 > _ONEHOT_VMEM_BYTES \
            and chunk % 2 == 0:
        chunk //= 2
    return chunk


def _first_of_leaf(leaf_ref, first_ref):
    i = pl.program_id(0)
    return i == first_ref[leaf_ref[i]]


def _amax_kernel(leaf_ref, first_ref, x_ref, base_ref, out_ref):
    m = jnp.max(jnp.abs(x_ref[...] - base_ref[...]), axis=1,
                keepdims=True)                           # [k, 1]
    first = _first_of_leaf(leaf_ref, first_ref)

    @pl.when(first)
    def _():
        out_ref[...] = m

    @pl.when(jnp.logical_not(first))
    def _():
        out_ref[...] = jnp.maximum(out_ref[...], m)


def _hist_kernel(leaf_ref, first_ref, len_ref, x_ref, base_ref, amax_ref,
                 out_ref, *, bins: int, block: int, chunk: int):
    """|tau| histogram of one block, padding-masked, accumulated into its
    leaf's [k, bins] row. One-hot per contribution row and column chunk:
    bins on sublanes against the chunk's bucket indices on lanes."""
    i = pl.program_id(0)
    leaf = leaf_ref[i]
    valid = len_ref[leaf] - (i - first_ref[leaf]) * block
    a = jnp.abs(x_ref[...] - base_ref[...])              # [k, B]
    # catalog._hist_quantile binning, verbatim: (a / amax * bins) as i32
    idx = jnp.clip((a / amax_ref[...] * bins).astype(jnp.int32),
                   0, bins - 1)
    k = a.shape[0]
    bucket = jax.lax.broadcasted_iota(jnp.int32, (bins, chunk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, bins), 0)
    counts = jnp.zeros((k, bins), jnp.float32)
    for r in range(k):
        for c in range(block // chunk):
            sl = idx[r:r + 1, c * chunk:(c + 1) * chunk]     # [1, chunk]
            live = ((lane + c * chunk) < valid).astype(jnp.float32)
            onehot = (sl == bucket).astype(jnp.float32)      # [bins, chunk]
            # counts of row r as a [1, bins] row: live @ onehot^T
            row = jax.lax.dot_general(
                live, onehot, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            counts = counts + jnp.where(rows == r, row, 0.0)
    first = _first_of_leaf(leaf_ref, first_ref)

    @pl.when(first)
    def _():
        out_ref[...] = counts

    @pl.when(jnp.logical_not(first))
    def _():
        out_ref[...] = out_ref[...] + counts


def _ties_block_kernel(leaf_ref, x_ref, base_ref, thr_ref, out_ref):
    out_ref[...] = ties_tile(x_ref[...], base_ref[...], thr_ref[...])


def _grid(num_prefetch: int, npad: int, block: int, in_specs, out_specs):
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_prefetch, grid=(npad // block,),
        in_specs=in_specs, out_specs=out_specs)


def _stream_specs(k: int, block: int):
    return [pl.BlockSpec((k, block), lambda i, *_: (0, i)),
            pl.BlockSpec((1, block), lambda i, *_: (0, i))]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def block_amax_pallas(stacked, base, leaf_id, first, *, block: int,
                      interpret: bool):
    """[k, Np] fp32 -> per-leaf max|x - base|, shape [L, k, 1].

    `leaf_id`: [nblocks] int32 leaf of each block; `first`: [L] int32
    first block of each leaf (see `batch_layout`)."""
    k, npad = stacked.shape
    nleaf = first.shape[0]
    return pl.pallas_call(
        _amax_kernel,
        grid_spec=_grid(2, npad, block, _stream_specs(k, block),
                        leaf_row_spec(k, 1)),
        out_shape=jax.ShapeDtypeStruct((nleaf, k, 1), jnp.float32),
        interpret=interpret,
    )(leaf_id, first, stacked, base)


@functools.partial(jax.jit,
                   static_argnames=("bins", "block", "interpret"))
def block_hist_pallas(stacked, base, leaf_id, first, lengths, amax, *,
                      bins: int, block: int, interpret: bool):
    """Per-leaf |tau| histograms: [L, k, bins] fp32 integer counts.

    `lengths`: [L] int32 true leaf lengths (padding is masked out);
    `amax`: [L, k, 1] fp32 per-leaf bin scale (already + 1e-12)."""
    k, npad = stacked.shape
    nleaf = first.shape[0]
    kern = functools.partial(_hist_kernel, bins=bins, block=block,
                             chunk=_hist_chunk(bins, block))
    return pl.pallas_call(
        kern,
        grid_spec=_grid(3, npad, block,
                        _stream_specs(k, block) + [leaf_row_spec(k, 1)],
                        leaf_row_spec(k, bins)),
        out_shape=jax.ShapeDtypeStruct((nleaf, k, bins), jnp.float32),
        interpret=interpret,
    )(leaf_id, first, lengths, stacked, base, amax)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ties_block_pallas(stacked, base, leaf_id, thr, *, block: int,
                      interpret: bool):
    """Fused TIES merge with per-leaf [L, k, 1] thresholds."""
    k, npad = stacked.shape
    return pl.pallas_call(
        _ties_block_kernel,
        grid_spec=_grid(1, npad, block,
                        _stream_specs(k, block) + [leaf_row_spec(k, 1)],
                        pl.BlockSpec((1, block), lambda i, *_: (0, i))),
        out_shape=jax.ShapeDtypeStruct((1, npad), jnp.float32),
        interpret=interpret,
    )(leaf_id, stacked, base, thr)


def hist_thresholds(counts, lengths, amax, trim: float, bins: int):
    """Resolve per-(leaf, contribution) trim thresholds from histograms.

    `counts`: [L, k, bins] fp32 integer counts; `lengths`: [L] true
    (unpadded) leaf lengths; `amax`: [L, k] (already + 1e-12). The cdf /
    argmax / scale sequence is `catalog._hist_quantile` verbatim so the
    resolved thresholds match the eager oracle bitwise.
    """
    cdf = jnp.cumsum(counts, axis=2) / \
        lengths.astype(jnp.float32)[:, None, None]
    bucket = jnp.argmax(cdf >= trim, axis=2)             # first crossing
    return (bucket.astype(jnp.float32) / bins) * amax    # [L, k]


def ties_hist_batch(stacked, base, leaf_id, first, lengths, *,
                    trim: float, bins: int, block: int,
                    interpret: bool) -> jax.Array:
    """Histogram-trim TIES over a block-aligned flat batch, 3 passes.

    `stacked`: [k, Np] fp32, L leaves each padded to a block multiple
    then concatenated; `base`: [1, Np]; `leaf_id`: [nblocks] int32 leaf
    index per block; `first`: [L] int32 first block per leaf; `lengths`:
    [L] int32 true leaf lengths. Returns [1, Np] fp32.
    """
    amax = block_amax_pallas(stacked, base, leaf_id, first, block=block,
                             interpret=interpret) + 1e-12  # [L, k, 1]
    counts = block_hist_pallas(stacked, base, leaf_id, first, lengths,
                               amax, bins=bins, block=block,
                               interpret=interpret)       # [L, k, bins]
    thr = hist_thresholds(counts, lengths, amax[:, :, 0], trim, bins)
    return ties_block_pallas(stacked, base, leaf_id, thr[:, :, None],
                             block=block, interpret=interpret)


def batch_layout(lengths, block: int) -> Tuple[jax.Array, jax.Array, int]:
    """Block -> leaf map for a block-aligned concatenation of leaves.

    `lengths`: python ints, true element count per leaf. Returns
    (leaf_id [nb] int32, first block per leaf [L] int32, total padded
    length).
    """
    leaf_id, first = [], []
    for li, n in enumerate(lengths):
        first.append(len(leaf_id))
        leaf_id.extend([li] * max(1, -(-n // block)))
    return (jnp.asarray(leaf_id, jnp.int32), jnp.asarray(first, jnp.int32),
            len(leaf_id) * block)
