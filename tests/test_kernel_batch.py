"""Kernel-frontier flat-batch dispatch: byte-identity property grids
for histogram-trim TIES, counter-RNG DARE, and int8 merge-on-arrival,
plus the engine routes, KernelEnv plumbing, and note_meta scale
threading.

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
from repro.core.resolve import clear_cache
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
    monkeypatch.setenv("REPRO_KERNEL_QUANTIZED", "0")
    monkeypatch.setenv("REPRO_KERNEL_DARE_RNG", "1")
    kernel_env.reset()
    assert kernel_env.resolve_interpret() is True
    assert kernel_env.block == 512
    assert kernel_env.hist_bins == 128
    assert kernel_env.quantized is False
    assert kernel_env.dare_kernel_rng is True


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


# ------------------------------------------------------- engine routes ---


def _tree_contribs(k=3, seed=11):
    rng = np.random.default_rng(seed)
    return [{"a": jnp.asarray(rng.standard_normal((8, 33)), jnp.float32),
             "b": jnp.asarray(rng.standard_normal(257), jnp.float32)}
            for _ in range(k)]


def test_engine_ties_hist_route_matches_exact_path():
    """ties + trim_method=histogram batches through the 3-launch kernel
    pipeline (dispatch counter proves it) and agrees with the unfused
    exact execution to fp32 tolerance."""
    contribs = _tree_contribs()
    base = jax.tree_util.tree_map(jnp.zeros_like, contribs[0])
    cache = engine.EngineCache()
    plan = engine.plan_merge([engine.contrib_meta(c) for c in contribs],
                             "ties", base=base, trim_method="histogram")
    got = engine.execute_plan(plan, contribs, base=base, use_cache=False,
                              pallas=True, max_batch_bytes=1 << 20,
                              cache=cache)
    assert cache.obs.counter("kernel_dispatch_total").value(
        kernel="ties_hist") >= 1
    want = engine.execute_plan(plan, contribs, base=base,
                               use_cache=False, cache=engine.EngineCache())
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


def test_engine_quant_route_zero_dequant():
    """Quantized contributions merge through the int8 kernel without
    EVER densifying a leaf: dequant_leaves stays 0 and the
    engine_quant_leaves_merged_total counter covers every task."""
    contribs = _tree_contribs(seed=12)
    cts = [compress_tree(c) for c in contribs]
    cache = engine.EngineCache()
    plan = engine.plan_merge([engine.contrib_meta(c) for c in cts],
                             "weight_average")
    got = engine.execute_plan(plan, cts, use_cache=False, pallas=True,
                              max_batch_bytes=1 << 20, cache=cache)
    assert cache.stats["dequant_leaves"] == 0
    assert cache.obs.counter("engine_quant_leaves_merged_total").value() == 2
    assert cache.obs.counter("kernel_dispatch_total").value(
        kernel="quant_nary") >= 1
    # agrees with dequantize-then-merge on the dense trees
    dense = [decompress_tree(c) for c in cts]
    want = engine.execute_plan(
        engine.plan_merge([engine.contrib_meta(c) for c in dense],
                          "weight_average"),
        dense, use_cache=False, cache=engine.EngineCache())
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


def test_engine_quant_route_respects_toggle():
    """REPRO_KERNEL_QUANTIZED=0 falls back to dequantize-then-merge
    (dequant counter fires, quant kernel does not)."""
    kernel_env.quantized = False
    cts = [compress_tree(c) for c in _tree_contribs(seed=13)]
    cache = engine.EngineCache()
    plan = engine.plan_merge([engine.contrib_meta(c) for c in cts],
                             "weight_average")
    engine.execute_plan(plan, cts, use_cache=False, pallas=True,
                        max_batch_bytes=1 << 20, cache=cache)
    assert cache.stats["dequant_leaves"] > 0
    assert cache.obs.counter("kernel_dispatch_total").value(
        kernel="quant_nary") == 0


def test_engine_dare_route_opt_in():
    """The DARE kernel route is off by default (its counter RNG is a
    different sampler than the catalog's `jax.random`); opting in via
    kernel_env routes the batch through it, deterministically, and
    byte-identically to the ops-level flat batch with the plan's
    per-task seeds."""
    contribs = _tree_contribs(seed=14)
    base = jax.tree_util.tree_map(jnp.zeros_like, contribs[0])
    metas = [engine.contrib_meta(c) for c in contribs]
    plan = engine.plan_merge(metas, "dare", base=base, seed=5)
    cache = engine.EngineCache()
    engine.execute_plan(plan, contribs, base=base, use_cache=False,
                        pallas=True, max_batch_bytes=1 << 20, cache=cache)
    assert cache.obs.counter("kernel_dispatch_total").value(
        kernel="dare") == 0                      # default: off
    kernel_env.dare_kernel_rng = True
    cache2 = engine.EngineCache()
    got = engine.execute_plan(plan, contribs, base=base, use_cache=False,
                              pallas=True, max_batch_bytes=1 << 20,
                              cache=cache2)
    assert cache2.obs.counter("kernel_dispatch_total").value(
        kernel="dare") >= 1
    again = engine.execute_plan(plan, contribs, base=base,
                                use_cache=False, pallas=True,
                                max_batch_bytes=1 << 20,
                                cache=engine.EngineCache())
    for g, a in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(again)):
        assert np.asarray(g).tobytes() == np.asarray(a).tobytes()
    # ops-level oracle: seed = plan.seed + task.index, leaf order by task
    leaves0 = jax.tree_util.tree_leaves(contribs[0])
    stacked = [jnp.stack([jax.tree_util.tree_leaves(c)[t.index]
                          .reshape(-1) for c in contribs])
               for t in plan.tasks]
    bases = [jnp.zeros(s.shape[1], jnp.float32) for s in stacked]
    want = ops.dare_batch_merge(
        stacked, bases, [plan.seed + t.index for t in plan.tasks], 0.5)
    got_leaves = jax.tree_util.tree_leaves(got)
    for t, w in zip(plan.tasks, want):
        g = got_leaves[t.index]
        assert np.asarray(g).reshape(-1).tobytes() == \
            np.asarray(w).tobytes()
    assert len(leaves0) == len(plan.tasks)


def test_kernel_routes_never_poison_exact_cache():
    """A pallas=True histogram-TIES merge with caching enabled must not
    leave approximate leaves for a later exact merge to return."""
    clear_cache()
    contribs = _tree_contribs(seed=15)
    base = jax.tree_util.tree_map(jnp.zeros_like, contribs[0])
    kw = dict(base=base, trim_method="histogram")
    engine.merge(contribs, "ties", pallas=True,
                 max_batch_bytes=1 << 20, **kw)   # use_cache defaults True
    exact = engine.merge(contribs, "ties", **kw)
    clear_cache()
    legacy = engine.merge(contribs, "ties", **kw)
    for a, b in zip(jax.tree_util.tree_leaves(exact),
                    jax.tree_util.tree_leaves(legacy)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    clear_cache()


def test_engine_integer_leaves_take_eager_path():
    """Integer-dtype leaves never enter the fp32 kernel routes (the
    _unpad truncation guard would otherwise be reachable)."""
    rng = np.random.default_rng(16)
    contribs = [{"ids": jnp.asarray(rng.integers(0, 9, 64), jnp.int32),
                 "w": jnp.asarray(rng.standard_normal(64), jnp.float32)}
                for _ in range(3)]
    got = engine.merge(contribs, "weight_average", use_cache=False,
                       pallas=True, max_batch_bytes=1 << 20)
    want = engine.merge(contribs, "weight_average", use_cache=False)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=1e-5, atol=1e-5)


# ------------------------------------------------- meta scale threading ---


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
    assert mq.scale_of(0) == mq.scales[0]
    assert md.scale_of(0) is None


def test_note_meta_threads_scales_into_plan():
    """note_meta(scales=) lands on the LeafTask: the planner prices
    int8 wire payloads at 1 byte/element and marks the task quantized."""
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
    assert task.quantized
    assert task.scales == (m.scales[0], m.scales[0])
    # int8 pricing: k * numel * 1 byte, not * 4
    assert task.stacked_nbytes == 2 * 300
