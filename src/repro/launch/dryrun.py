"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we jit the appropriate step function (train_step for train
shapes; prefill / decode_step for serving shapes) with explicit in/out
shardings over the production mesh, lower against ShapeDtypeStruct inputs
(no allocation), compile, and record:

  - compiled.memory_analysis()  (per-device bytes: proves it fits)
  - compiled.cost_analysis()    (per-device HLO FLOPs / bytes accessed)
  - collective traffic parsed from the optimized HLO text
  - analytic MODEL_FLOPS for the roofline "useful compute" ratio

Artifacts are written to experiments/dryrun/<cell>.json and consumed by
benchmarks/roofline.py.

`main()` gives the host platform 512 devices (`XLA_FLAGS`) before JAX
starts its backend; importing this module changes nothing.
"""
import argparse
import json
import os
import re
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, list_archs
from repro.data.synthetic import batch_shapes
from repro.launch.compile_cache import place_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model
from repro.sharding import policy
from repro.train.step import make_train_step, train_state_shapes

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]))\S*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def collective_traffic(hlo_text: str) -> Dict[str, float]:
    """Approximate per-device collective traffic (bytes) from compiled HLO.

    all-gather: result; all-reduce: 2x result; reduce-scatter: result;
    all-to-all: result; collective-permute: result. (Ring-algorithm
    (n-1)/n factors are folded into ~1; see EXPERIMENTS.md §Roofline.)
    """
    out: Dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_txt, op = m.group(1), m.group(2)
        size = _shape_bytes(shape_txt)
        mult = 2.0 if op == "all-reduce" else 1.0
        out[op] = out.get(op, 0.0) + mult * size
    return out


def input_specs(arch: str, shape_name: str, *, smoke: bool = False,
                shape_override=None):
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    from repro.configs import smoke_config
    cfg = smoke_config(arch) if smoke else get_config(arch)
    shape = shape_override or SHAPES[shape_name]
    bs = batch_shapes(cfg, shape)
    batch = {k: jax.ShapeDtypeStruct(s, jnp.dtype(dt))
             for k, (s, dt) in bs.items()}
    return cfg, shape, batch


VARIANTS = {
    "castbf16": lambda c: c.replace(cast_params_for_loss=True),
    "headpad16": lambda c: c.replace(pad_heads_to_tp=16),
    "accum2": lambda c: c.replace(grad_accum=2),
    "accum4": lambda c: c.replace(grad_accum=4),
    "accum16": lambda c: c.replace(grad_accum=16),
    "optbf16": lambda c: c.replace(opt_state_dtype="bfloat16"),
    "parambf16": lambda c: c.replace(param_dtype="bfloat16"),
    "qchunk1k": lambda c: c.replace(attn_q_chunk=1024),
    "noremat": lambda c: c.replace(remat="none"),
    "bf16psum": lambda c: c.replace(bf16_psum=True),
    "optint8": lambda c: c.replace(opt_state_dtype="int8"),
}


def apply_variant(cfg, variant: str):
    """'castbf16+accum4' -> composed config transform."""
    for tok in (variant or "base").split("+"):
        if tok in ("", "base"):
            continue
        cfg = VARIANTS[tok](cfg)
    return cfg


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                moe_impl: str = "gather", out_dir: Optional[str] = None,
                donate: bool = True, mesh=None, smoke: bool = False,
                shape_override=None, variant: str = "base") -> Dict:
    cfg, shape, batch_sds = input_specs(arch, shape_name, smoke=smoke,
                                        shape_override=shape_override)
    base_cfg = cfg                     # MODEL_FLOPS from the unmodified arch
    cfg = apply_variant(cfg, variant)
    if shape_name == "long_500k" and not cfg.supports_long_context:
        result = {"arch": arch, "shape": shape_name, "status": "SKIP",
                  "kind": shape.kind, "variant": variant,
                  "moe_impl": moe_impl,
                  "reason": "full-attention arch; long_500k needs "
                            "sub-quadratic attention (see DESIGN.md)"}
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            suffix = "mp" if multi_pod else "sp"
            fname = (f"{arch}__{shape_name}__{suffix}__{moe_impl}__"
                     f"{(variant or 'base').replace('+', '_')}.json")
            with open(os.path.join(out_dir, fname), "w") as f:
                json.dump(result, f, indent=1)
        return result
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg, moe_impl=moe_impl)
    policy.set_mesh(mesh)
    t0 = time.time()
    result = {"arch": arch, "shape": shape_name,
              "mesh": dict(mesh.shape), "chips": mesh.size,
              "moe_impl": moe_impl, "kind": shape.kind,
              "variant": variant}
    try:
        if shape.kind == "train":
            state_sds = train_state_shapes(model)
            state_sh = policy.state_shardings(model, mesh, state_sds)
            batch_sh = policy.batch_shardings(mesh, batch_sds)
            step = make_train_step(model)
            jitted = jax.jit(
                step, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, NamedSharding(mesh, P())),
                donate_argnums=(0,) if donate else ())
            lowered = jitted.lower(state_sds, batch_sds)
        elif shape.kind == "prefill":
            psh = policy.params_shardings(model, mesh)
            p_sds = _cast_params(model)
            batch_sh = policy.batch_shardings(mesh, batch_sds)
            jitted = jax.jit(lambda p, b: model.prefill(p, b),
                             in_shardings=(psh, batch_sh))
            lowered = jitted.lower(p_sds, batch_sds)
        else:  # decode
            psh = policy.params_shardings(model, mesh)
            p_sds = _cast_params(model)
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cache_sh = policy.cache_shardings(model, mesh, cache_sds)
            tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(
                lambda p, c, t, q: model.decode_step(p, c, t, q),
                in_shardings=(psh, cache_sh,
                              policy.batch_shardings(mesh, {"t": tok})["t"],
                              NamedSharding(mesh, P())),
                out_shardings=(NamedSharding(mesh, P()), cache_sh),
                donate_argnums=(1,) if donate else ())
            lowered = jitted.lower(p_sds, cache_sds, tok, pos)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        ca = compiled.cost_analysis() or {}
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
        # trip-count-aware cost model (XLA's cost_analysis counts while
        # bodies once — see repro.launch.hlo_cost)
        from repro.launch.hlo_cost import analyze as hlo_analyze
        rep = hlo_analyze(hlo)
        result.update({
            "status": "OK",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "flops_per_device": rep.flops,
            "dot_flops_per_device": rep.dot_flops,
            "elementwise_flops_per_device": rep.elementwise_flops,
            "bytes_accessed_per_device": rep.bytes_accessed,
            "xla_body_once_flops": ca.get("flops", 0.0),
            "xla_body_once_bytes": ca.get("bytes accessed", 0.0),
            "peak_memory_per_device": getattr(ma, "peak_memory_in_bytes", 0),
            "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
            "output_bytes": getattr(ma, "output_size_in_bytes", 0),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
            "collectives_per_device": rep.collective_bytes,
            "collective_counts": rep.collective_count,
            "collective_bytes_per_device": rep.total_collective_bytes,
            "collective_top": [
                [b, op, shp] for b, op, shp in
                sorted(rep.collective_details, reverse=True)[:10]],
            "unknown_trip_whiles": rep.unknown_trip_whiles,
        })
        result.update(_model_flops(base_cfg, shape))
    except Exception as e:  # record failures as artifacts too
        result.update({"status": "FAIL", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]})
    finally:
        policy.set_mesh(None)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = "mp" if multi_pod else "sp"
        vtag = (variant or "base").replace("+", "_")
        fname = f"{arch}__{shape_name}__{suffix}__{moe_impl}__{vtag}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(result, f, indent=1)
    return result


def dryrun_merge_cell(arch: str, *, k: int = 4, strategy: str = "ties",
                      multi_pod: bool = False,
                      out_dir: Optional[str] = None,
                      trim_method: str = "quantile",
                      dtype: str = "bfloat16") -> Dict:
    """Roofline cell for the PAPER'S TECHNIQUE: a sharded k-way Layer-2
    merge of full model parameters on the production mesh. The merge is
    elementwise over the parameter shards (the CRDT wrapper moves no
    tensors), so the bound is HBM bandwidth — except for exact-quantile
    TIES trims, whose per-row selection over a whole leaf is a global
    operation the histogram trim removes (§Perf)."""
    from repro.strategies import get_strategy
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg)
    policy.set_mesh(mesh)
    result = {"arch": arch, "shape": f"merge_k{k}_{strategy}",
              "mesh": dict(mesh.shape), "chips": mesh.size,
              "kind": "merge", "variant": trim_method}
    try:
        dt = jnp.dtype(dtype)
        p_sds = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, dt),
            model.param_shapes())
        psh = policy.params_shardings(model, mesh)
        strat = get_strategy(strategy)
        kw = {"trim_method": trim_method} if strategy == "ties" else {}

        def merge_fn(contribs, base):
            return strat(contribs, base=base, seed=42, **kw)

        t0 = time.time()
        lowered = jax.jit(merge_fn,
                          in_shardings=([psh] * k, psh),
                          out_shardings=psh).lower([p_sds] * k, p_sds)
        compiled = lowered.compile()
        t_compile = time.time() - t0
        from repro.launch.hlo_cost import analyze as hlo_analyze
        rep = hlo_analyze(compiled.as_text())
        ma = compiled.memory_analysis()
        total, _ = cfg.param_counts()
        result.update({
            "status": "OK", "compile_s": round(t_compile, 2),
            "flops_per_device": rep.flops,
            "dot_flops_per_device": rep.dot_flops,
            "bytes_accessed_per_device": rep.bytes_accessed,
            "peak_memory_per_device": getattr(ma, "peak_memory_in_bytes", 0),
            "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
            "collectives_per_device": rep.collective_bytes,
            "collective_bytes_per_device": rep.total_collective_bytes,
            "params_total": total,
            # one-pass lower bound: read k contributions + base, write out
            "bytes_lower_bound_per_device":
                (k + 2) * total * dt.itemsize / mesh.size,
            "model_flops": 0.0, "tokens": 0,
        })
    except Exception as e:
        result.update({"status": "FAIL", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]})
    finally:
        policy.set_mesh(None)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = "mp" if multi_pod else "sp"
        fname = f"{arch}__merge_k{k}_{strategy}_{trim_method}__{suffix}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(result, f, indent=1)
    return result


def _cast_params(model: Model):
    dt = jnp.dtype(model.cfg.param_dtype)
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dt), model.param_shapes())


def _model_flops(cfg, shape) -> Dict:
    """Analytic 'useful' FLOPs for the roofline ratio."""
    from repro.models.params import count_params, non_embedding_params
    total, active = count_params(cfg)
    ne_total, ne_active = non_embedding_params(cfg)
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        tokens = b * s
        mf = 6.0 * ne_active * tokens
    elif shape.kind == "prefill":
        tokens = b * s
        mf = 2.0 * ne_active * tokens
    else:
        tokens = b            # one token per sequence
        mf = 2.0 * ne_active * tokens
    return {"params_total": total, "params_active": active,
            "model_flops": mf, "tokens": tokens}


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--moe-impl", default="gather",
                    choices=["gather", "einsum"])
    ap.add_argument("--variant", default="base",
                    help="'+'-joined perf variants: " + ",".join(VARIANTS))
    ap.add_argument("--merge", action="store_true",
                    help="lower the paper's merge step instead of train/serve")
    ap.add_argument("--merge-strategy", default="ties")
    ap.add_argument("--merge-k", type=int, default=4)
    ap.add_argument("--trim-method", default="quantile",
                    choices=["quantile", "histogram"])
    ap.add_argument("--out", default="experiments/dryrun")
    vb = ap.add_mutually_exclusive_group()
    vb.add_argument("--quiet", action="store_true",
                    help="no stdout output")
    vb.add_argument("--verbose", action="store_true",
                    help="print structured JSON events instead of text")
    ap.add_argument("--events-out", default="",
                    help="also write the event stream to this JSONL file")
    args = ap.parse_args()

    from repro.obs import EventLog
    log = EventLog.from_args(args)
    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = (list(SHAPES) if args.shape == "all" else [args.shape])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    n_ok = n_fail = n_skip = 0
    if args.merge:
        for arch in archs:
            for mp in meshes:
                r = dryrun_merge_cell(
                    arch, k=args.merge_k, strategy=args.merge_strategy,
                    multi_pod=mp, out_dir=args.out,
                    trim_method=args.trim_method)
                if r["status"] == "OK":
                    log.emit(
                        "cell_ok",
                        f"[OK]   {arch:24s} {r['shape']:20s} "
                        f"{r['variant']:10s} "
                        f"bytes/dev={r['bytes_accessed_per_device']:.3e} "
                        f"(bound {r['bytes_lower_bound_per_device']:.3e}) "
                        f"coll="
                        f"{r['collective_bytes_per_device']/2**20:.1f}MiB",
                        arch=arch, kind="merge", status="OK")
                else:
                    n_fail += 1
                    log.emit("cell_fail",
                             f"[FAIL] {arch:24s} merge {r['error']}",
                             arch=arch, kind="merge", status="FAIL",
                             error=r["error"])
        if args.events_out:
            log.dump(args.events_out)
        if n_fail:
            raise SystemExit(1)
        return
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                r = dryrun_cell(arch, shape_name, multi_pod=mp,
                                moe_impl=args.moe_impl, out_dir=args.out,
                                variant=args.variant)
                mesh = "2x16x16" if mp else "16x16"
                tag = f"{arch:24s} {shape_name:12s} {mesh:8s}"
                if r["status"] == "OK":
                    n_ok += 1
                    log.emit(
                        "cell_ok",
                        f"[OK]   {tag} flops/dev={r['flops_per_device']:.3e} "
                        f"peak={r['peak_memory_per_device']/2**30:.2f}GiB "
                        f"coll="
                        f"{r['collective_bytes_per_device']/2**20:.1f}MiB "
                        f"compile={r['compile_s']:.1f}s",
                        arch=arch, shape=shape_name, status="OK")
                elif r["status"] == "SKIP":
                    n_skip += 1
                    log.emit("cell_skip", f"[SKIP] {tag} {r['reason']}",
                             arch=arch, shape=shape_name, status="SKIP",
                             reason=r["reason"])
                else:
                    n_fail += 1
                    log.emit("cell_fail", f"[FAIL] {tag} {r['error']}",
                             arch=arch, shape=shape_name, status="FAIL",
                             error=r["error"])
    log.emit("done", f"done: {n_ok} ok, {n_skip} skip, {n_fail} fail",
             ok=n_ok, skip=n_skip, fail=n_fail)
    if args.events_out:
        log.dump(args.events_out)
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
