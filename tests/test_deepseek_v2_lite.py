"""DeepSeek-V2-Lite's expert-parallel share, and the executor's chains
in blocks.

At the published widths only shapes are built (`jax.eval_shape`); every
other test runs a tiny model with the same tree (MLA with a direct q
projection, a stacked MoE share with 2 shared experts, a leading dense
block, an untied head) on seeded random weights:

  (a) the chip benchmark's configuration file is the share's tree;
  (b) the share layers, their shared experts counted once, add up to a
      plain float32 forward of the uncut layer written from the paper;
  (c) a merge of fine-tunes of the share is the held-expert slice of
      the whole model's merge, bit for bit, for the elementwise
      strategies;
  (d) `Replica.resolve` on the share equals `reference_apply` and the
      chip benchmark's plain reference, bit for bit;
  (e) TIES, and the linear folds over one leaf or a fused batch, run
      block by block equal the whole run, bit for bit, accumulators
      included; the batch cap does not grow with another leaf's k;
  (f) the executor's counters of (e) count as documented.
"""
import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import MergeSpec, Replica
from repro.configs import get_config, MLAConfig
from repro.configs.deepseek_v2_lite_16b import CONFIG, EP8_SHARE, \
    expert_share
from repro.core import engine
from repro.core.resolve import reference_apply
from repro.models import moe as MOE
from repro.models.model import Model
from repro.models.schema import init_from_schema, PDef
from repro.strategies import get_strategy

ROOT = Path(__file__).resolve().parents[1]
BENCH_CONFIG = ROOT / "benchmarks/chip/configs/deepseek-v2-lite-5L-ep8.json"


def _load_chip_reference():
    spec = importlib.util.spec_from_file_location(
        "chip_reference", ROOT / "benchmarks/chip/reference.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the same tree as the published model at a size the CPU runs: 8 routed
# experts top-3 (capacity large enough that no token is dropped, so the
# uncut reference, which has no capacity, is the layer), 2 shared
TINY = CONFIG.replace(
    name="dsv2lite-tiny", n_layers=3, d_model=32, n_heads=4, n_kv_heads=4,
    head_dim=8, d_ff=48, vocab_size=64, attn_q_chunk=16, remat="none",
    mla=MLAConfig(kv_lora_rank=16, q_lora_rank=0, d_head_nope=8,
                  d_head_rope=4, d_head_v=8),
    moe=dataclasses.replace(CONFIG.moe, num_experts=8, top_k=3,
                            d_ff_expert=16, d_ff_shared=16,
                            capacity_factor=8.0))
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _paths(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {tuple(k.key for k in p): x for p, x in flat}


# ------------------------------------------------------------ (a) config


def test_benchmark_config_file_is_the_share_tree():
    data = json.loads(BENCH_CONFIG.read_text())
    shapes = _paths(jax.eval_shape(Model(EP8_SHARE).init,
                                   jax.random.PRNGKey(0)))
    schema = jax.tree_util.tree_flatten_with_path(
        Model(EP8_SHARE).schema(), is_leaf=lambda x: isinstance(x, PDef))[0]
    inits = {tuple(k.key for k in p): d.init for p, d in schema}
    assert [(tuple(x["path"]), tuple(x["shape"]), x["init"])
            for x in data["leaves"]] == [
        (p, tuple(s.shape), inits[p]) for p, s in sorted(shapes.items())]
    assert len(data["leaves"]) == 29
    assert data["params"] == 535060992 == sum(
        int(np.prod(s.shape)) for s in shapes.values())
    assert shapes[("blocks", "sub0", "ffn", "experts", "w_gate")].shape \
        == (4, 8, 2048, 1408)
    assert shapes[("blocks", "sub0", "ffn", "router")].shape \
        == (4, 2048, 64)
    # what is cut, and the published widths beside it
    assert data["n_layers"] == EP8_SHARE.n_layers == 5
    assert data["n_routed_experts_held"] == EP8_SHARE.moe.held[1] == 8
    assert data["vocab_rows"] == EP8_SHARE.vocab_size == 12800
    assert (data["num_hidden_layers"], data["n_routed_experts"],
            data["vocab_size"]) == (CONFIG.n_layers, CONFIG.moe.num_experts,
                                    CONFIG.vocab_size)
    m = CONFIG.mla
    assert (data["hidden_size"], data["intermediate_size"],
            data["moe_intermediate_size"], data["num_attention_heads"],
            data["kv_lora_rank"], data["qk_nope_head_dim"],
            data["qk_rope_head_dim"], data["v_head_dim"],
            data["num_experts_per_tok"], data["n_shared_experts"],
            data["norm_topk_prob"], data["q_lora_rank"]) == (
        CONFIG.d_model, CONFIG.d_ff, CONFIG.moe.d_ff_expert,
        CONFIG.n_heads, m.kv_lora_rank, m.d_head_nope, m.d_head_rope,
        m.d_head_v, CONFIG.moe.top_k, CONFIG.moe.num_shared_experts,
        CONFIG.moe.norm_topk_prob, m.q_lora_rank or None)


def test_full_expert_sets_build_the_tree_they_built_before():
    """With the default share (every expert held) the stacks hold every
    routed expert, and naming the whole range changes nothing."""
    for name in ("deepseek-v2-236b", "qwen3-moe-30b-a3b"):
        cfg = get_config(name)
        shapes = _paths(Model(cfg).param_shapes())
        e = cfg.moe.num_experts
        assert shapes[("blocks", "sub0", "ffn", "experts", "w_up")].shape \
            == (Model(cfg).n_periods, e, cfg.d_model, cfg.moe.d_ff_expert)
        whole = cfg.replace(moe=dataclasses.replace(
            cfg.moe, expert_start=0, experts_held=e))
        assert Model(whole).param_shapes() == Model(cfg).param_shapes()
    with pytest.raises(ValueError):
        dataclasses.replace(CONFIG.moe, expert_start=60, experts_held=8)


# ---------------------------------------------------------- (b) the layer


def _ffn(w_gate, w_up, w_down, h):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def reference_moe_layer(p, x, top_k, held=None):
    """DeepSeek-V2's MoE layer (arXiv:2405.04434 section 2.2) in float32:
    softmax scores over every routed expert, the top-k kept with their
    scores as gates (not renormalised), the routed experts' SwiGLU
    outputs weighted by them, plus the shared experts. `held` (a range
    of expert ids) keeps only those experts' routed terms. Returns
    (routed, shared)."""
    logits = jnp.einsum("gsd,de->gse", x, p["router"])
    scores = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(scores, top_k)
    e = p["router"].shape[1]
    weights = jnp.sum(jax.nn.one_hot(idx, e) * gates[..., None], axis=-2)
    if held is not None:
        weights = weights * jnp.isin(jnp.arange(e), jnp.asarray(held))
    ex = p["experts"]
    outs = jnp.stack([_ffn(ex["w_gate"][j], ex["w_up"][j], ex["w_down"][j],
                           x) for j in range(e)], axis=-2)
    routed = jnp.einsum("gse,gsed->gsd", weights, outs)
    sh = p["shared"]
    return routed, _ffn(sh["w_gate"], sh["w_up"], sh["w_down"], x)


def _share_params(p, start, held):
    ex = {n: w[start:start + held] for n, w in p["experts"].items()}
    return {**p, "experts": ex}


def _layer(seed=0):
    params = init_from_schema(MOE.moe_def(TINY), jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (2, 16, TINY.d_model), jnp.float32)
    return params, x


# f32 sums of 8-48 products taken in another order than the reference's:
# ~1e-7 of the output's scale; a bf16 layer misses by ~1e-3
TOL = 1e-5


def _gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("impl", ["gather", "einsum"])
@pytest.mark.parametrize("ways", [2, 4, 8])
def test_shares_add_up_to_the_uncut_layer(impl, ways):
    params, x = _layer()
    m = TINY.moe
    with jax.default_matmul_precision("highest"):
        routed, shared = reference_moe_layer(params, x, m.top_k)
        total = jnp.zeros_like(x)
        for rank in range(ways):
            cfg = expert_share(TINY, ways=ways, rank=rank, n_layers=3,
                               vocab_rows=64)
            start, held = cfg.moe.held
            y, _ = MOE.moe_block(_share_params(params, start, held), x,
                                 cfg, jnp.float32, impl=impl)
            own, _ = reference_moe_layer(params, x, m.top_k,
                                         held=range(start, start + held))
            # each share: its own experts' part plus the shared experts
            assert _gap(y, own + shared) < TOL
            total = total + (y - shared)
        assert _gap(total + shared, routed + shared) < TOL


def test_tokens_routed_elsewhere_get_the_shared_experts_alone():
    params, x = _layer(seed=3)
    cfg = expert_share(TINY, ways=8, rank=5, n_layers=3, vocab_rows=64)
    with jax.default_matmul_precision("highest"):
        y, _ = MOE.moe_block(_share_params(params, 5, 1), x, cfg,
                             jnp.float32)
        _, shared = reference_moe_layer(params, x, TINY.moe.top_k)
        idx = jax.lax.top_k(jax.nn.softmax(x @ params["router"]),
                            TINY.moe.top_k)[1]
    elsewhere = ~jnp.any(idx == 5, axis=-1)
    assert bool(jnp.any(elsewhere)) and bool(jnp.any(~elsewhere))
    assert np.array_equal(np.asarray(y[elsewhere]),
                          np.asarray(shared[elsewhere])) or \
        _gap(y[elsewhere], shared[elsewhere]) < TOL


def test_bf16_layer_misses_the_tolerance():
    params, x = _layer()
    with jax.default_matmul_precision("highest"):
        routed, shared = reference_moe_layer(params, x, TINY.moe.top_k)
        y, _ = MOE.moe_block(params, x, TINY, jnp.bfloat16)
    assert _gap(y.astype(jnp.float32), routed + shared) > 10 * TOL


def test_share_model_runs_its_forward():
    cfg = expert_share(TINY, ways=4, rank=1, n_layers=3, vocab_rows=64)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 64)
    loss, mets = jax.jit(model.loss)(params, {"tokens": tokens})
    assert np.isfinite(float(loss)) and np.isfinite(float(mets["aux"]))


# ----------------------------------------------------------- (c) and (d)


def _fine_tunes(params, k=3, seed=10):
    def tune(j):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(jax.random.PRNGKey(seed + j), len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [
            (x.astype(jnp.float32) + 0.002 * jax.random.normal(
                kk, x.shape, jnp.float32)).astype(x.dtype)
            for kk, x in zip(keys, leaves)])
    return [tune(j) for j in range(k)]


def _bf16_model(cfg, seed=0):
    params = Model(cfg).init(jax.random.PRNGKey(seed))
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)


def _slice_share(tree, start, held):
    def cut(path, x):
        keys = tuple(k.key for k in path)
        if "experts" in keys and keys[-1] in EXPERT_LEAVES:
            return x[:, start:start + held]
        return x
    return jax.tree_util.tree_map_with_path(cut, tree)


def _resolve(strategy, contribs, base=None, cfg=None):
    rep = Replica("t")
    eids = [rep.contribute(c) for c in contribs]
    spec = MergeSpec(strategy, cfg or {})
    if base is not None:
        spec = MergeSpec(strategy, cfg or {},
                         base_ref=rep.register_base(base))
    out = rep.resolve(spec)
    order = sorted(range(len(contribs)), key=lambda j: eids[j])
    return out, [contribs[j] for j in order], rep


def _bitwise(a, b):
    return a.dtype == b.dtype and a.shape == b.shape and \
        np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("strategy,pin", [("weight_average", False),
                                          ("task_arithmetic", True)])
def test_share_merge_is_the_slice_of_the_whole_merge(strategy, pin):
    base = _bf16_model(TINY)
    tunes = _fine_tunes(base)
    whole, _, _ = _resolve(strategy, tunes, base if pin else None)
    start, held = 2, 2                   # rank 1 of 4
    share, _, _ = _resolve(strategy,
                           [_slice_share(t, start, held) for t in tunes],
                           _slice_share(base, start, held) if pin else None)
    want = _paths(_slice_share(whole, start, held))
    got = _paths(share)
    assert set(got) == set(want)
    assert all(_bitwise(got[p], want[p]) for p in got)


@pytest.mark.parametrize("strategy,pin", [("ties", True),
                                          ("weight_average", False),
                                          ("task_arithmetic", True)])
def test_share_resolve_equals_the_references(strategy, pin):
    cfg = expert_share(TINY, ways=4, rank=3, n_layers=3, vocab_rows=64)
    base = _bf16_model(cfg, seed=1)
    tunes = _fine_tunes(base, seed=20)
    got, ordered, _ = _resolve(strategy, tunes, base if pin else None)
    b = base if pin else None
    ref = reference_apply(strategy, ordered, base=b)
    chip = dict(_load_chip_reference().merged_leaves(strategy, ordered, b,
                                                     {}))
    flat = jax.tree_util.tree_flatten_with_path(got)[0]
    ref_by = {jax.tree_util.keystr(p): x for p, x in
              jax.tree_util.tree_flatten_with_path(ref)[0]}
    assert set(chip) == set(ref_by) == {jax.tree_util.keystr(p)
                                        for p, _ in flat}
    for p, x in flat:
        path = jax.tree_util.keystr(p)
        assert _bitwise(x, ref_by[path]), path
        assert _bitwise(x, chip[path]), path


# ---------------------------------------------------------- (e) and (f)


def test_leading_blocks_cover_the_array_in_row_major_order():
    for shape, n, sizes in [((5, 7, 6), 3, [1, 2, 2]),
                            ((13, 9), 4, [3, 3, 3, 4]),
                            ((4, 8, 6), 5, None), ((3,), 9, [1, 1, 1])]:
        blocks = engine.leading_blocks(shape, n)
        assert len(blocks) >= min(n, shape[0])
        a = np.arange(np.prod(shape)).reshape(shape)
        flat = np.concatenate([a[blk].reshape(-1) for blk in blocks])
        assert np.array_equal(flat, np.arange(a.size))
        if sizes is not None:
            assert [b[0].stop - b[0].start for b in blocks] == sizes
    assert len(engine.leading_blocks((4, 8, 6), 5)) == 8     # 4 x 2


def _ties_inputs(shape, k, dtype, seed):
    key = jax.random.PRNGKey(seed)
    b = (0.02 * jax.random.normal(key, shape)).astype(dtype)
    noise = 0.002 * jax.random.normal(jax.random.fold_in(key, 1),
                                      (k,) + shape)
    s = (b.astype(jnp.float32) + noise).astype(dtype)
    s = s.at[0, 0].set(b[0])               # some task-vector entries 0
    return s, b


BLOCK_CASES = [((5, 7, 6), 3), ((5, 7, 6), 7), ((13, 9), 4), ((4, 8, 6), 5)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("k", [2, 3])
@pytest.mark.parametrize("shape,n", BLOCK_CASES)
def test_blocked_ties_is_bitwise_the_whole_leaf(dtype, k, shape, n):
    s, b = _ties_inputs(shape, k, dtype, seed=k + n)
    strat = get_strategy("ties")
    blocks = engine.leading_blocks(shape, n)
    assert len(blocks) > 1
    got = strat.blocked(**strat.defaults)(s, b, blocks)
    assert _bitwise(got, strat.apply_leaf(s, b))
    assert strat.blocked(trim_method="histogram") is None


def _free_for(budget):
    # what the device would have free for a chain to have `budget`
    return 2 * budget


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("k", [2, 3])
def test_executor_slices_ties_leaves_bitwise(monkeypatch, dtype, k):
    big, small = (5, 7, 6), (3, 4)
    sb, bb = _ties_inputs(big, k, dtype, seed=1)
    ss, bs = _ties_inputs(small, k, dtype, seed=2)
    contribs = [{"big": sb[j], "small": ss[j]} for j in range(k)]
    base = {"big": bb, "small": bs}

    def run(free):
        monkeypatch.setattr(engine, "_free_bytes", lambda _: free)
        cache = engine.EngineCache()
        plan = engine.plan_for(contribs, "ties", base=base)
        return engine.execute_plan(plan, contribs, base=base,
                                   use_cache=False, cache=cache), cache

    whole, c0 = run(None)
    stacked = k * 5 * 7 * 6 * jnp.dtype(dtype).itemsize
    # in 2 blocks (rows 2, 3 of 5) the stack and one block's chain come
    # to 10.6 stacks, in 4 (rows 1, 1, 1, 2) to 7.4: 8 allows 4 blocks;
    # the small leaf's whole chain fits
    sliced, c1 = run(_free_for(8 * stacked))
    for name in base:
        assert _bitwise(sliced[name], whole[name]), name
    assert c0.obs.counter("sliced_leaves_total").value(strategy="ties") == 0
    assert c1.obs.counter("sliced_leaves_total").value(strategy="ties") == 1
    assert c1.obs.counter("leaf_slices_total").value(strategy="ties") == 4
    # reckoned: the larger of the threshold stage and one block's chain
    # (rows 2 of 5) beside the stack
    assert c1.obs.gauge("engine_working_set_bytes").value() == max(
        engine._THRESHOLD_STACKS * stacked,
        stacked + engine._CHAIN_STACKS * stacked * 2 // 5)
    assert c0.obs.gauge("engine_working_set_bytes").value() == \
        (1 + engine._CHAIN_STACKS) * stacked
    c1.reset_exec_stats()
    assert c1.obs.gauge("engine_working_set_bytes").value() == 0


def test_blocks_stop_at_the_chain_stacks(monkeypatch):
    # where nothing fits, the blocks stop at _CHAIN_STACKS: past that a
    # block's chain is below a stack
    blocks, _ = engine._chain_blocks((64,), 1024, None)
    assert blocks == [()]
    monkeypatch.setattr(engine, "_free_bytes", lambda _: 0)
    blocks, ws = engine._chain_blocks((64,), 1024, None)
    assert len(blocks) == engine._CHAIN_STACKS
    assert ws == engine._CHAIN_STACKS * 1024 * 4 // 64


def test_other_strategies_and_the_histogram_trim_stay_whole(monkeypatch):
    s, b = _ties_inputs((13, 9), 3, jnp.float32, seed=4)
    contribs = [{"w": s[j]} for j in range(3)]
    monkeypatch.setattr(engine, "_free_bytes", lambda _: 0)
    for strategy, cfg in [("ties", {"trim_method": "histogram"}),
                          ("dare", {}), ("slerp", {}),
                          ("linear", {})]:
        cache = engine.EngineCache()
        plan = engine.plan_for(contribs[:2] if strategy == "linear"
                               else contribs, strategy, base={"w": b},
                               **cfg)
        engine.execute_plan(plan, contribs[:2] if strategy == "linear"
                            else contribs, base={"w": b},
                            use_cache=False, cache=cache)
        assert cache.obs.counter("sliced_leaves_total").value(
            strategy=strategy) == 0, strategy


def _fold_inputs(k, dtype):
    shapes = {"big": (5, 7, 6), "w1": (3, 4), "w2": (6,)}
    base = {n: (0.02 * jax.random.normal(jax.random.PRNGKey(i), sh)
                ).astype(dtype) for i, (n, sh) in enumerate(shapes.items())}
    contribs = [{n: (x.astype(jnp.float32) + 0.002 * jax.random.normal(
        jax.random.PRNGKey(100 + 10 * j + i), x.shape)).astype(dtype)
        for i, (n, x) in enumerate(base.items())} for j in range(k)]
    return contribs, base


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("strategy,pin", [("weight_average", False),
                                          ("task_arithmetic", True)])
def test_executor_runs_folds_in_blocks_bitwise(monkeypatch, dtype,
                                               strategy, pin):
    """A fold over one leaf and over a fused batch, in blocks, gives the
    whole run's values and accumulators, and a fold resumed from the
    blocks' accumulator gives the whole recompute's value."""
    contribs, base = _fold_inputs(4, dtype)
    b = base if pin else None

    def run(free, cs):
        monkeypatch.setattr(engine, "_free_bytes", lambda _: free)
        cache = engine.EngineCache()
        plan = engine.plan_for(cs, strategy, base=b)
        out = engine.execute_plan(plan, cs, base=b, cache=cache)
        return out, cache, plan

    whole, c0, plan = run(None, contribs[:3])
    sliced, c1, _ = run(0, contribs[:3])
    assert c0.stats["batched_leaves"] == c1.stats["batched_leaves"] == 2
    for name in base:
        assert _bitwise(sliced[name], whole[name]), name
    for t in plan.tasks:
        assert _bitwise(c1.aux(t.sub_root), c0.aux(t.sub_root)), t.path
    # the big leaf in 5 x 4 blocks, the batch of w1 and w2 in 16
    assert c1.obs.counter("sliced_leaves_total").value(
        strategy=strategy) == 3
    assert c1.obs.counter("leaf_slices_total").value(
        strategy=strategy) == 20 + 16
    assert c0.obs.counter("sliced_leaves_total").value(
        strategy=strategy) == 0
    # resumed from the blocked accumulators, in blocks again
    plan4 = engine.plan_for(contribs, strategy, base=b)
    resumed = engine.execute_plan(plan4, contribs, base=b, cache=c1)
    assert c1.stats["fold_resumes"] == 3
    monkeypatch.setattr(engine, "_free_bytes", lambda _: None)
    fresh = engine.execute_plan(plan4, contribs, base=b, use_cache=False,
                                cache=engine.EngineCache())
    for name in base:
        assert _bitwise(resumed[name], fresh[name]), name


def test_fold_blocks_do_not_grow_with_k(monkeypatch):
    """A fold is waited for step by step, so its working set is reckoned
    on one contribution's leaf: the same blocks at k 2 and k 8, and no
    compile of a new block shape as an append-only window grows."""
    key = jax.random.PRNGKey(11)
    base = {"w": jax.random.normal(key, (16, 8))}
    contribs = [{"w": base["w"] + 0.01 * jax.random.normal(
        jax.random.fold_in(key, j), (16, 8))} for j in range(1, 9)]
    one = 16 * 8 * 4
    # exactly what half of the free bytes holds, whole
    monkeypatch.setattr(engine, "_free_bytes",
                        lambda _: 2 * engine._CHAIN_STACKS * one)
    for k in (2, 8):
        cache = engine.EngineCache()
        plan = engine.plan_for(contribs[:k], "task_arithmetic", base=base)
        out = engine.execute_plan(plan, contribs[:k], base=base,
                                  cache=cache)
        assert cache.obs.counter("sliced_leaves_total").value(
            strategy="task_arithmetic") == 0
        assert cache.obs.gauge("engine_working_set_bytes").value() == \
            engine._CHAIN_STACKS * one
        monkeypatch.setattr(engine, "_free_bytes", lambda _: None)
        ref = engine.execute_plan(plan, contribs[:k], base=base,
                                  use_cache=False, cache=engine.EngineCache())
        assert _bitwise(out["w"], ref["w"])
        monkeypatch.setattr(engine, "_free_bytes",
                            lambda _: 2 * engine._CHAIN_STACKS * one)


def test_batch_cap_does_not_grow_with_another_leafs_k():
    """The default batch cap is the largest leaf's stack at the batch's
    own k: a sparse leaf whose k grows does not make the dense leaves
    fuse."""
    key = jax.random.PRNGKey(7)

    def leaf(j, n):
        return jax.random.normal(jax.random.fold_in(key, 10 * n + j), (n,))
    base = {"g": leaf(0, 10), "w1": leaf(0, 8), "w2": leaf(0, 6)}
    dense = [{n: leaf(j, x.shape[0]) for n, x in base.items()}
             for j in (1, 2)]
    sparse = [{"g": leaf(j, 10)} for j in range(3, 7)]
    contribs = dense + sparse
    coverages = [None, None] + [("['g']",)] * len(sparse)
    plan = engine.plan_for(contribs, "task_arithmetic", base=base,
                           coverages=coverages)

    def run(**kw):
        cache = engine.EngineCache()
        engine.execute_plan(plan, contribs, base=base, use_cache=False,
                            cache=cache, **kw)
        return cache.stats["batched_leaves"]
    # g (k 6) runs alone; w1 and w2 (k 2) together hold more than the
    # largest leaf, g, at k 2, so they run alone too
    assert run() == 0
    # a cap of g's stack at its own k would fuse them
    assert run(max_batch_bytes=6 * 10 * 4) == 2


def test_replica_metrics_show_the_sliced_leaves(monkeypatch):
    s, b = _ties_inputs((13, 9), 3, jnp.bfloat16, seed=5)
    stacked = 3 * 13 * 9 * 2
    # 4 blocks (rows 3, 3, 3, 4 of 13): the stack and 16 x 4/13 stacks
    monkeypatch.setattr(engine, "_free_bytes",
                        lambda _: _free_for(6 * stacked))
    out, _, rep = _resolve("ties", [{"w": s[j]} for j in range(3)],
                           {"w": b})
    metrics = rep.metrics()
    assert metrics["sliced_leaves_total{strategy=ties}"] == 1
    assert metrics["leaf_slices_total{strategy=ties}"] == 4
    assert metrics["engine_working_set_bytes"] > 0
