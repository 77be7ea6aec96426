"""Bring-up smoke of the merge path on one TPU chip.

    python chip_smoke.py [--seed N]

Runs the system's main path once, in one process, through the entry
points a user calls: `Replica.contribute` (Layer 1: hashing, OR-Set
add), `Replica.merge` (the join, both ways), then `Replica.resolve` ->
`resolve_spec` -> the merge engine (Layer 2) for `weight_average`,
`ties` and `dare`. The model is phi3-mini-3.8b at its published widths
(d_model 3072, 32 x 96 heads, d_ff 8192 SwiGLU, vocab 32064, untied) in
bf16, cut by depth only; the base and the k contributions (base plus
seeded deltas) are generated on the device from `--seed`.

Checks, each of which fails the run:
  * two replicas that received the contributions in different orders
    (one with a duplicate) reach equal Merkle roots after merging both
    ways, and resolve to bitwise-equal pytrees;
  * each resolve agrees with `core.resolve.reference_apply` on the same
    device: bitwise, or within the stated tolerance (reported);
  * the Pallas kernel routes `nary_accum`, `ties_hist` and `quant_nary`
    dispatch compiled (kernel_dispatch_total counts every fused group)
    and agree with their `kernels/ref.py` oracles within the tolerance.

Exits non-zero, printing no result, when JAX finds no TPU or Pallas
would run in interpret mode. The last stdout line is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.api import MergeSpec, Replica  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import engine  # noqa: E402
from repro.core.compression import compress_tree  # noqa: E402
from repro.core.resolve import reference_apply, seed_from_root  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.config import kernel_env  # noqa: E402
from repro.launch.compile_cache import place_compile_cache  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.strategies import get_strategy  # noqa: E402

ARCH = "phi3-mini-3.8b"
# 2 of 32 layers: 0.42 G parameters, 0.85 GB a bf16 copy; every leaf
# keeps its published width. Resident on the chip at once: the base and
# k=3 contributions, a replica's output, the whole-model reference's own
# [k, ...] stack of every leaf and its output, and one leaf's transients
# (~4 GB for TIES on the 32064 x 3072 embedding). At 4 layers that sum
# passes the 16 GB of one v5e chip.
LAYERS = 2
K = 3
DELTA_SCALE = 0.002     # fine-tune deltas against the base's 0.02 init
# Tolerance where bits differ, per leaf: one bf16 ulp at the leaf's
# largest magnitude (2^-7 * max|ref|). Engine and reference (or kernel
# and oracle) run the same fp32 arithmetic; only the order of the k-axis
# reduction and the final rounding to bf16 may differ.
REL_ULP = 2.0 ** -7

_compile_s = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += duration


class Phase:
    """Wall and compile seconds of one phase, printed when it ends."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), _compile_s[0]
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            wall = time.perf_counter() - self.t0
            comp = _compile_s[0] - self.c0
            print(f"phase {self.name}: {wall:.3f} s wall, "
                  f"{comp:.3f} s compile, peak_bytes_in_use "
                  f"{peak_bytes()}", flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def device_check() -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        fail(f"no TPU: JAX found platform {d.platform!r}")
    if kernel_env.resolve_interpret():
        fail("Pallas kernels would run in interpret mode "
             "(REPRO_KERNEL_INTERPRET is set)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def ready(tree):
    return jax.block_until_ready(tree)


def make_weights(model: Model, seed: int, k: int):
    """bf16 base and k contributions (base + seeded deltas), on device."""
    bf16 = jnp.bfloat16

    @jax.jit
    def gen_base(key):
        return jax.tree_util.tree_map(lambda p: p.astype(bf16),
                                      model.init(key))

    @jax.jit
    def gen_contrib(base, key):
        leaves, treedef = jax.tree_util.tree_flatten(base)
        out = [(b.astype(jnp.float32) + DELTA_SCALE * jax.random.normal(
                    jax.random.fold_in(key, i), b.shape)).astype(bf16)
               for i, b in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, out)

    kb, *kc = jax.random.split(jax.random.PRNGKey(seed), k + 1)
    base = ready(gen_base(kb))
    return base, [ready(gen_contrib(base, key)) for key in kc]


def leaf_diff(got, want) -> tuple:
    """(bitwise equal, max |got - want|, tolerance) for one leaf."""
    bits = {2: jnp.uint16, 4: jnp.uint32}[got.dtype.itemsize]
    same = got.dtype == want.dtype and bool(jnp.array_equal(
        jax.lax.bitcast_convert_type(got, bits),
        jax.lax.bitcast_convert_type(want, bits)))
    w = want.astype(jnp.float32)
    diff = float(jnp.max(jnp.abs(got.astype(jnp.float32) - w)))
    return same, diff, REL_ULP * float(jnp.max(jnp.abs(w)))


def tree_diff(got, want) -> dict:
    rows = [leaf_diff(g, w) for g, w in zip(jax.tree_util.tree_leaves(got),
                                            jax.tree_util.tree_leaves(want))]
    return {"bitwise": all(r[0] for r in rows),
            "max_abs_diff": max(r[1] for r in rows),
            "within_tol": all(r[1] <= r[2] for r in rows)}


def bitwise_equal(a, b) -> bool:
    return all(leaf_diff(x, y)[0] for x, y in
               zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


def phase_sec(contribs):
    """Two replicas, different arrival orders (B sees a duplicate), then
    the join both ways."""
    ra, rb = Replica("replica-a"), Replica("replica-b")
    ids_a = [ra.contribute(contribs[i]) for i in (0, 1, 2)]
    ids_b = [rb.contribute(contribs[i]) for i in (2, 0, 2, 1)]
    if set(ids_a) != set(ids_b) or len(set(ids_a)) != K:
        fail("replicas named the same contributions differently")
    ra.merge(rb)
    rb.merge(ra)
    if ra.merkle_root() != rb.merkle_root() or ra.visible() != rb.visible():
        fail("Merkle roots differ after merging both ways")
    print(f"sec: {len(ra.visible())} visible on both replicas, "
          f"root {ra.merkle_root().hex()[:16]}", flush=True)
    return ra, rb


def phase_resolve(ra, rb, base, name: str) -> dict:
    spec = MergeSpec(name)
    with Phase(f"resolve[{name}] replica-a"):
        out_a = ready(ra.resolve(spec, base=base))
    with Phase(f"resolve[{name}] replica-b"):
        out_b = ready(rb.resolve(spec, base=base))
    if not bitwise_equal(out_a, out_b):
        fail(f"{name}: the two replicas resolved to different bytes")
    del out_b                   # HBM: the reference's transients follow
    ids = sorted(ra.visible())
    with Phase(f"reference[{name}]"):
        want = ready(reference_apply(
            name, [ra.state.store[i] for i in ids], base=base,
            seed=seed_from_root(ra.merkle_root()), reduction=spec.reduction,
            **spec.cfg_dict()))
    res = tree_diff(out_a, want)
    print(f"resolve[{name}]: replicas bitwise equal; vs reference_apply "
          f"{json.dumps(res)}", flush=True)
    if not (res["bitwise"] or res["within_tol"]):
        fail(f"{name}: engine differs from reference_apply beyond "
             f"tolerance ({res['max_abs_diff']})")
    return res


def _stack(contribs, i: int):
    """[k, n] fp32 rows of leaf i, in canonical contribution order."""
    return jnp.stack([jax.tree_util.tree_leaves(c)[i].reshape(-1)
                      for c in contribs]).astype(jnp.float32)


def phase_kernel(kernel: str, spec: MergeSpec, contribs, ids, base,
                 oracle) -> dict:
    """One kernel route through `engine.merge(..., pallas=True)`.

    The engine fuses same-dtype leaves into groups under its batch cap;
    a group of one leaf takes the exact eager path instead (covered by
    the resolve phase). Every fused group must dispatch the kernel — the
    counter proves there was no silent fallback — and every fused leaf
    must match the route's `kernels/ref.py` oracle."""
    cache = engine.EngineCache()
    with Phase(f"kernel[{kernel}]"):
        out = ready(engine.merge(contribs, contrib_ids=ids, base=base,
                                 spec=spec, pallas=True, cache=cache,
                                 use_cache=False))
    # the executor's own grouping, to know which leaves the kernel merged
    plan = engine.plan_for(contribs, contrib_ids=ids, base=base, spec=spec)
    strat = get_strategy(spec.strategy)
    groups = engine._dispatch_groups(
        strat, list(plan.tasks), None,
        max(t.stacked_nbytes // t.k for t in plan.tasks),
        fuse=engine._kernel_route(strat, spec.cfg_dict()) is not None)
    fused = [t for g in groups if len(g) > 1 for t in g]
    n_groups = sum(1 for g in groups if len(g) > 1)
    dispatched = cache.obs.counter("kernel_dispatch_total").value(
        kernel=kernel)
    if n_groups == 0 or dispatched != n_groups:
        fail(f"{kernel}: {dispatched} kernel dispatches for {n_groups} "
             "fused groups")
    outs = jax.tree_util.tree_leaves(out)
    bases = jax.tree_util.tree_leaves(base)
    rows = []
    for t in fused:
        got = outs[t.index]
        want = oracle(t.index, bases[t.index]).reshape(got.shape)
        rows.append(leaf_diff(got, want.astype(got.dtype)))
    res = {"dispatches": dispatched, "fused_leaves": len(fused),
           "exact_leaves": len(plan.tasks) - len(fused),
           "fused_elements": sum(int(outs[t.index].size) for t in fused),
           "bitwise": all(r[0] for r in rows),
           "max_abs_diff": max(r[1] for r in rows),
           "within_tol": all(r[1] <= r[2] for r in rows)}
    print(f"kernel[{kernel}]: {json.dumps(res)}", flush=True)
    if not (res["bitwise"] or res["within_tol"]):
        fail(f"{kernel}: kernel route differs from its oracle beyond "
             "tolerance")
    return res


def phase_kernels(ra, base) -> dict:
    ids = sorted(ra.visible())
    dense = [ra.state.store[i] for i in ids]
    w = jnp.full((K, 1), 1.0 / K, jnp.float32)
    trim, bins = 0.2, kernel_env.hist_bins

    def nary(i, b):
        return ref.nary_accum_ref(_stack(dense, i),
                                  jnp.zeros((1, b.size), jnp.float32), w)

    def ties(i, b):
        return ref.ties_hist_ref(_stack(dense, i),
                                 b.reshape(1, -1).astype(jnp.float32),
                                 trim, bins)

    results = {
        "nary_accum": phase_kernel(
            "nary_accum", MergeSpec("weight_average"), dense, ids, base,
            nary),
        "ties_hist": phase_kernel(
            "ties_hist", MergeSpec("ties", {"trim_method": "histogram"}),
            dense, ids, base, ties),
    }
    with Phase("quantize contributions (host)"):
        quant = [compress_tree(c) for c in dense]
    # the ids only memoize planner metadata; the int8 payloads need
    # their own (sub-roots derive from content digests, not ids)
    qids = [i + "-int8" for i in ids]

    def qnary(i, b):
        q = jnp.stack([jnp.asarray(c.leaves[i].q).reshape(-1)
                       for c in quant])
        s = jnp.asarray([float(c.leaves[i].scale) for c in quant],
                        jnp.float32)
        return ref.quant_nary_ref(q, s, jnp.zeros((1, b.size),
                                                  jnp.float32), w)

    results["quant_nary"] = phase_kernel(
        "quant_nary", MergeSpec("weight_average"), quant, qids, base, qnary)
    return results


def run(cfg, seed: int, full_layers: int) -> None:
    """Every phase, on `cfg` (phi3-mini-3.8b cut by depth)."""
    model = Model(cfg)
    with Phase("generate weights"):
        base, contribs = make_weights(model, seed, K)
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(base))
    nbytes = sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(base))
    print(f"model: {cfg.name} (d_model {cfg.d_model}, {cfg.n_heads}x"
          f"{cfg.resolved_head_dim} heads, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}), cut by depth to {cfg.n_layers} of "
          f"{full_layers} layers: {n} parameters, {nbytes} bytes a "
          f"{cfg.param_dtype} copy, k={K}, seed {seed}", flush=True)
    with Phase("contribute + join"):
        ra, rb = phase_sec(contribs)
    for name in ("weight_average", "ties", "dare"):
        phase_resolve(ra, rb, base, name)
    phase_kernels(ra, base)
    print(f"peak_bytes_in_use {peak_bytes()}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated base and contributions")
    args = ap.parse_args()
    device = device_check()
    cache_dir = place_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(f"device: {json.dumps(device)}; compile cache {cache_dir}",
          flush=True)
    full = get_config(ARCH)
    run(full.replace(n_layers=LAYERS, param_dtype="bfloat16"), args.seed,
        full.n_layers)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
