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
  * the library's flat-batch Pallas kernels `nary_accum`, `ties_hist`
    and `quant_nary`, called through `kernels.ops` on batches of the
    model's leaves (the merge engine does not call them), run compiled
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
from repro.core.compression import compress_tree  # noqa: E402
from repro.core.resolve import reference_apply, seed_from_root  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.config import kernel_env  # noqa: E402
from repro.launch.compile_cache import place_compile_cache  # noqa: E402
from repro.models.model import Model  # noqa: E402

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


def _rows(contribs, i: int):
    """[k, n] rows of leaf i in its own dtype, in canonical order."""
    return jnp.stack([jax.tree_util.tree_leaves(c)[i].reshape(-1)
                      for c in contribs])


def _batches(sizes) -> list:
    """Leaf indices packed largest first into batches of at most the
    largest leaf's elements, as a flat-batch dispatch holds them; a
    leaf that fills a batch alone is left out (a flat batch is of many
    leaves)."""
    cap = max(sizes)
    out, cur, n = [], [], 0
    for i in sorted(range(len(sizes)), key=lambda i: (-sizes[i], i)):
        if cur and n + sizes[i] > cap:
            out.append(cur)
            cur, n = [], 0
        cur.append(i)
        n += sizes[i]
    out.append(cur)
    return [b for b in out if len(b) > 1]


def phase_kernel(kernel: str, merge, oracle, batches) -> dict:
    """One flat-batch kernel of the library, called through
    `kernels.ops` on batches of the model's leaves. `merge(batch)`
    returns the batch's fp32 outputs, one flat row per leaf; each must
    match `oracle(i)`, the kernel's `kernels/ref.py` oracle on leaf i."""
    with Phase(f"kernel[{kernel}]"):
        outs = [ready(merge(b)) for b in batches]
    rows = [leaf_diff(o, oracle(i).reshape(-1))
            for b, out in zip(batches, outs) for i, o in zip(b, out)]
    res = {"dispatches": len(batches),
           "leaves": sum(len(b) for b in batches),
           "elements": sum(int(o.size) for out in outs for o in out),
           "bitwise": all(r[0] for r in rows),
           "max_abs_diff": max(r[1] for r in rows),
           "within_tol": all(r[1] <= r[2] for r in rows)}
    print(f"kernel[{kernel}]: {json.dumps(res)}", flush=True)
    if not (res["bitwise"] or res["within_tol"]):
        fail(f"{kernel}: kernel differs from its oracle beyond tolerance")
    return res


def _split(flat, batch, sizes) -> list:
    offs = [0]
    for i in batch:
        offs.append(offs[-1] + sizes[i])
    return [flat[lo:hi] for lo, hi in zip(offs, offs[1:])]


def phase_kernels(ra, base) -> dict:
    ids = sorted(ra.visible())
    dense = [ra.state.store[i] for i in ids]
    bases = [b.reshape(-1).astype(jnp.float32)
             for b in jax.tree_util.tree_leaves(base)]
    sizes = [int(b.size) for b in bases]
    batches = _batches(sizes)
    weights = [1.0 / K] * K
    w = jnp.full((K, 1), 1.0 / K, jnp.float32)
    trim, bins = 0.2, kernel_env.hist_bins

    def zeros(i):
        return jnp.zeros((sizes[i],), jnp.float32)

    def nary(batch):
        # weight_average: out = sum_i w_i x_i, over a zero base
        stacked = jnp.concatenate([_rows(dense, i) for i in batch], axis=1)
        flat = ops.nary_flat_merge(
            stacked, jnp.zeros((stacked.shape[1],), jnp.float32), weights,
            preserve_dtype=True)
        return _split(flat, batch, sizes)

    def nary_ref(i):
        return ref.nary_accum_ref(_rows(dense, i).astype(jnp.float32),
                                  zeros(i)[None, :], w)

    def ties(batch):
        return ops.ties_batch_merge([_rows(dense, i) for i in batch],
                                    [bases[i] for i in batch], trim)

    def ties_ref(i):
        return ref.ties_hist_ref(_rows(dense, i).astype(jnp.float32),
                                 bases[i][None, :], trim, bins)

    results = {
        "nary_accum": phase_kernel("nary_accum", nary, nary_ref, batches),
        "ties_hist": phase_kernel("ties_hist", ties, ties_ref, batches),
    }
    with Phase("quantize contributions (host)"):
        quant = [compress_tree(c) for c in dense]

    def q_rows(i):
        return jnp.stack([jnp.asarray(c.leaves[i].q).reshape(-1)
                          for c in quant])

    def q_scales(i):
        return jnp.asarray([float(c.leaves[i].scale) for c in quant],
                           jnp.float32)

    def qnary(batch):
        return ops.quant_batch_merge([q_rows(i) for i in batch],
                                     [q_scales(i) for i in batch],
                                     [zeros(i) for i in batch], weights)

    def qnary_ref(i):
        return ref.quant_nary_ref(q_rows(i), q_scales(i), zeros(i)[None, :],
                                  w)

    results["quant_nary"] = phase_kernel("quant_nary", qnary, qnary_ref,
                                         batches)
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
