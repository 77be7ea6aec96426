"""Kernel library flat-batch dispatch: byte-identity property grids
for histogram-trim TIES, counter-RNG DARE, and int8 merge-on-arrival,
plus KernelEnv plumbing and note_meta scale threading.

Byte-identity contract (DESIGN.md §6): kernel outputs are compared
against the jit-compiled eager reference for arithmetic done inside the
jitted driver (quant), and against the eager reference for the
histogram pipeline (its threshold math runs host-side op-by-op in both
the kernel driver and the reference). Op-by-op vs jitted eager can
differ by an FMA-contraction ulp, so each test states its oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.compression import compress_tree, decompress_tree
from repro.kernels import ops, ref
from repro.kernels.common import pad_flat, pad_stacked, pad_stacked_raw
from repro.kernels.config import kernel_env
from repro.kernels.dare import dare_pallas

BLOCK = 256           # small block: length grid hits many boundaries
# odd lengths straddling block boundaries, exact multiples, tiny leaves
LENGTHS = [1, 7, 100, 255, 256, 257, 511, 512, 1000]
KS = [1, 16]


@pytest.fixture(autouse=True)
def _restore_kernel_env():
    yield
    kernel_env.reset()


def _leaves(k, lengths, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    ls = [jnp.asarray(rng.standard_normal((k, n)), dtype)
          for n in lengths]
    bs = [jnp.asarray(rng.standard_normal(n), jnp.float32)
          for n in lengths]
    return ls, bs


# ------------------------------------------------------------ ops level ---


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ties_batch_byte_identity_grid(k, dtype):
    """Flat-batch histogram TIES == per-leaf reference, bitwise,
    across odd lengths at block boundaries. The oracle evaluates the
    threshold eagerly on the unpadded row (exact regardless of layout)
    and the merge jitted on the block-padded layout the kernel sees —
    the interpret-mode kernel body is a jitted computation, and XLA
    CPU's jitted axis-0 reduction differs from the op-by-op one by an
    ulp at k=16. bf16 upcasts to fp32 at stack time on both sides."""
    leaves, bases = _leaves(k, LENGTHS, dtype)
    outs = ops.ties_batch_merge(leaves, bases, 0.2, block=BLOCK,
                                interpret=True)
    bins = kernel_env.hist_bins
    jties = jax.jit(ref.ties_ref)
    for o, s, b, n in zip(outs, leaves, bases, LENGTHS):
        s32 = s.astype(jnp.float32)
        thr = ref.hist_threshold_ref(s32, b[None, :], 0.2, bins)
        sp, _ = pad_stacked(s32, BLOCK)
        bp, _ = pad_flat(b, BLOCK)
        r = jties(sp, bp[None, :], thr).reshape(-1)[:n]
        assert np.array_equal(np.asarray(o), np.asarray(r)), f"n={n}"


@pytest.mark.parametrize("k", KS)
def test_ties_batch_invariant_to_batching(k):
    """The tentpole claim directly: merging a leaf inside a flat batch
    returns the same bytes as dispatching it alone."""
    leaves, bases = _leaves(k, LENGTHS, seed=5)
    batched = ops.ties_batch_merge(leaves, bases, 0.2, block=BLOCK,
                                   interpret=True)
    for o, s, b, n in zip(batched, leaves, bases, LENGTHS):
        solo = ops.ties_batch_merge([s], [b], 0.2, block=BLOCK,
                                    interpret=True)[0]
        assert np.asarray(o).tobytes() == np.asarray(solo).tobytes(), \
            f"n={n}"


def test_ties_trim_tau_boundary():
    """Values sitting exactly on a histogram bucket edge (|tau| an
    exact multiple of amax/bins) resolve to the same side in the
    batched kernel and the reference — the >= threshold comparison is
    computed from identical bits on both paths."""
    bins = kernel_env.hist_bins
    n = 512
    # tau = m * (amax/bins) for m in 0..bins-1, plus the max element
    amax = jnp.float32(1.0)
    tau = (jnp.arange(n, dtype=jnp.float32) % bins) * (amax / bins)
    tau = tau.at[0].set(amax)
    base = jnp.zeros(n, jnp.float32)
    s = (base + tau)[None, :]
    out = ops.ties_batch_merge([s], [base], 0.2, block=BLOCK,
                               interpret=True)[0]
    r = ref.ties_hist_ref(s, base[None, :], 0.2, bins=bins)
    assert np.array_equal(np.asarray(out), np.asarray(r).reshape(-1))


@pytest.mark.parametrize("k", KS)
def test_dare_batch_byte_identity_grid(k):
    """Flat-batch DARE == per-leaf kernel dispatch with the same seed,
    bitwise: the counter RNG is indexed by (row, global column), and
    the batch threads each leaf's npad/start offsets through the
    per-block metadata, so batching cannot change a single draw."""
    leaves, bases = _leaves(k, LENGTHS, seed=1)
    seeds = [31 + i for i in range(len(LENGTHS))]
    outs = ops.dare_batch_merge(leaves, bases, seeds, 0.5, block=BLOCK,
                                interpret=True)
    for o, s, b, n, sd in zip(outs, leaves, bases, LENGTHS, seeds):
        sp, _ = pad_stacked(s, BLOCK)
        bp, _ = pad_flat(b, BLOCK)
        r = dare_pallas(sp, bp[None, :],
                        jnp.asarray([[sd]], jnp.uint32), p=0.5,
                        block=BLOCK, interpret=True)
        assert np.array_equal(np.asarray(o),
                              np.asarray(r).reshape(-1)[:n]), f"n={n}"


@pytest.mark.parametrize("k", KS)
def test_quant_batch_byte_identity_grid(k):
    """int8 merge-on-arrival == jit-compiled dequantize-then-merge
    reference, bitwise (the jitted oracle: the kernel's mul+add runs
    inside one jitted computation, so XLA contracts to FMA on both
    sides identically)."""
    rng = np.random.default_rng(2)
    _, bases = _leaves(k, LENGTHS, seed=2)
    qs = [jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
          for n in LENGTHS]
    scales = [jnp.asarray(rng.random(k) * 0.01 + 1e-4, jnp.float32)
              for _ in LENGTHS]
    w = jnp.asarray(rng.random(k), jnp.float32)
    outs = ops.quant_batch_merge(qs, scales, bases, w, block=BLOCK,
                                 interpret=True)
    jref = jax.jit(ref.quant_nary_ref)
    for o, q, sc, b, n in zip(outs, qs, scales, bases, LENGTHS):
        qp, _ = pad_stacked_raw(q, BLOCK)        # same layout as the tile
        bp, _ = pad_flat(b, BLOCK)
        r = jref(qp, sc, bp[None, :], w.reshape(-1, 1))
        assert np.array_equal(np.asarray(o),
                              np.asarray(r).reshape(-1)[:n]), f"n={n}"
        solo = ops.quant_batch_merge([q], [sc], [b], w, block=BLOCK,
                                     interpret=True)[0]
        assert np.asarray(o).tobytes() == np.asarray(solo).tobytes()


def test_ties_merge_trim_method_routing():
    """`trim_method="histogram"` (default) rides the batched kernel;
    "quantile" keeps the exact sort path; anything else raises."""
    contribs, base = ([jnp.asarray(np.random.default_rng(3)
                                   .standard_normal(300), jnp.float32)
                       for _ in range(3)],
                      jnp.zeros(300, jnp.float32))
    hist = ops.ties_merge(contribs, base, interpret=True)
    quant = ops.ties_merge(contribs, base, trim_method="quantile",
                           interpret=True)
    assert hist.shape == quant.shape == (300,)
    # same pipeline, different threshold estimator: close, not equal
    np.testing.assert_allclose(np.asarray(hist), np.asarray(quant),
                               atol=0.5)
    with pytest.raises(ValueError):
        ops.ties_merge(contribs, base, trim_method="sorted",
                       interpret=True)


def test_unpad_rejects_integer_target_dtype():
    """fp32 kernel output must never silently truncate into an integer
    leaf dtype."""
    with pytest.raises(TypeError):
        ops._unpad(jnp.ones((1, 8), jnp.float32), 4, (4,), jnp.int32)


# ---------------------------------------------------------- KernelEnv ---


def test_kernel_env_env_overrides(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    monkeypatch.setenv("REPRO_KERNEL_BLOCK", "512")
    monkeypatch.setenv("REPRO_KERNEL_HIST_BINS", "128")
    kernel_env.reset()
    assert kernel_env.resolve_interpret() is True
    assert kernel_env.block == 512
    assert kernel_env.hist_bins == 128


def test_kernel_env_rejects_bad_values(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BLOCK", "0")
    with pytest.raises(ValueError):
        kernel_env.reset()
    monkeypatch.delenv("REPRO_KERNEL_BLOCK")
    monkeypatch.setenv("REPRO_KERNEL_HIST_BINS", "1")
    with pytest.raises(ValueError):
        kernel_env.reset()
    monkeypatch.delenv("REPRO_KERNEL_HIST_BINS")
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "maybe")
    with pytest.raises(ValueError):
        kernel_env.reset()


def test_kernel_env_drives_ops_defaults(monkeypatch):
    """ops wrappers read block/interpret from the env singleton when
    the caller passes None."""
    kernel_env.block = 64
    kernel_env.interpret = True
    contribs, base = ([jnp.asarray(np.random.default_rng(4)
                                   .standard_normal(130), jnp.float32)
                       for _ in range(2)],
                      jnp.zeros(130, jnp.float32))
    out = ops.ties_merge(contribs, base)       # no block/interpret kwargs
    explicit = ops.ties_merge(contribs, base, block=64, interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(explicit))


# ------------------------------------------------- meta scale threading ---


def _tree_contribs(k=3, seed=11):
    rng = np.random.default_rng(seed)
    return [{"a": jnp.asarray(rng.standard_normal((8, 33)), jnp.float32),
             "b": jnp.asarray(rng.standard_normal(257), jnp.float32)}
            for _ in range(k)]


def test_contrib_meta_quantized_digests_match_dense():
    """Content identity is defined on dequantized tensors: a quantized
    contribution's per-leaf digests equal the digests of its dense
    form, and the meta carries per-leaf scales."""
    tree = _tree_contribs(k=1, seed=17)[0]
    ct = compress_tree(tree)
    mq = engine.contrib_meta(ct)
    md = engine.contrib_meta(decompress_tree(ct))
    assert mq.digests == md.digests
    assert mq.scales is not None and all(
        s is not None for s in mq.scales)
    assert md.scales is None


def test_note_meta_threads_scales_into_plan():
    """note_meta(scales=) keeps the announced scales on the meta, and
    the planner prices the leaf's stack at its dense width: the
    executor densifies every int8 slice before it stacks it."""
    tree = {"a": jnp.asarray(np.random.default_rng(18)
                             .standard_normal(300), jnp.float32)}
    ct = compress_tree(tree)
    m = engine.contrib_meta(ct, eid="e" * 64)
    m2 = engine.note_meta("f" * 64, list(m.paths), list(m.digests),
                          [tuple(s) for s in m.shapes],
                          [str(d) for d in m.dtypes],
                          scales=list(m.scales))
    assert m2.scales == m.scales
    plan = engine.plan_merge([m, m2], "weight_average")
    (task,) = plan.tasks
    # dense pricing: k * numel * 4 bytes (float32), not the int8 width
    assert task.stacked_nbytes == 2 * 300 * 4
