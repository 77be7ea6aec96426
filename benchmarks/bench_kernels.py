"""Merge-kernel benchmarks + roofline gates (DESIGN.md §6).

Two jobs:

1. ``main(quick)`` — the usual ``benchmarks/run.py`` section: wall-clock
   rows (interpret on CPU; compiled on TPU) plus the analytic
   HBM-traffic rows that motivate the fusion.

2. ``gates(quick)`` / ``python -m benchmarks.bench_kernels --out f.json``
   — the CI regression gate. On CI CPUs, interpret-mode wall clocks say
   nothing about TPU behaviour, so every gate is either an EXACT
   bytes-moved / pass-count accounting of the kernel pipelines (checked
   against the eager op-graph's traffic) or a byte-identity check
   against the jit-compiled eager reference. Non-zero exit on any
   failed gate.

Traffic model. Fused side: the histogram-TIES pipeline is exactly three
passes over the flat batch (amax, histogram, merge — kernels/histogram).
Eager side: one kernel launch per jnp op, i.e. each op reads every
input element once from HBM and writes every output element once. XLA's
elementwise fusion narrows this in practice, but cannot close it: the
catalog pipeline has three reductions, a scatter-add histogram, and
multiple consumers of ``tau``/``trimmed``, each of which forces a
materialisation boundary. The per-op enumeration is the honest model of
the unfused graph and is reported op by op in the JSON artifact.

Byte-identity contract: kernels are compared against the **jit-compiled**
eager reference (``jax.jit(ref.*)``). Op-by-op eager execution can
differ by 1 ulp on CPU because XLA contracts mul+add into FMA inside a
jitted computation but not between separately-dispatched eager ops.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.roofline import bandwidth_bound_s, HBM_BW
from repro.kernels import ops, ref
from repro.kernels.common import pad_flat, pad_stacked, pad_stacked_raw
from repro.kernels.dare import dare_pallas

Row = Tuple[str, float, str]

ELEM = 4        # fp32 bytes
TIES_GATE_RATIO = 3.0      # fused TIES must move >= 3x fewer HBM bytes


# ------------------------------------------------------------- traffic ---


def ties_hist_fused_traffic(k: int, p: int, bins: int = 512) -> Dict:
    """Exact element counts for the fused histogram-TIES pipeline.

    Three grid passes over the flat batch (kernels/histogram.py):
      amax:  read k*p (stack) + p (base); write k per leaf (negligible)
      hist:  read k*p + p + k amax;       write k*bins counts per leaf
      merge: read k*p + p + k thresholds; write p merged elements
    Host-side threshold math touches only [k, bins] arrays.
    """
    elems = 3 * (k * p + p) + p + k * bins
    return {"elems": elems, "bytes": elems * ELEM, "passes": 3}


def ties_hist_eager_ops(k: int, p: int, bins: int = 512) -> List[Tuple]:
    """Op-by-op traffic of ``strategies.catalog._ties_nd_histogram``
    under the one-kernel-per-op model (read every input element, write
    every output element; no inter-op fusion). Returns
    ``[(op, read_elems, write_elems), ...]`` in program order."""
    kp, kb = k * p, k * bins
    return [
        ("tau = s - b", kp + p, kp),
        ("a = abs(tau)", kp, kp),
        ("amax = max(a, axis=1..)", kp, k),
        ("a / amax", kp + k, kp),
        ("* bins", kp, kp),
        (".astype(int32)", kp, kp),
        ("clip(.., 0, bins-1)", kp, kp),
        ("scatter-add counts", kp + kb, kb),
        ("cumsum(counts)", kb, kb),
        ("cdf / n", kb, kb),
        ("cdf >= trim", kb, kb),
        ("argmax(.., axis=1)", kb, k),
        ("thr = bucket/bins*amax", 3 * k, k),
        ("mask = a >= thr", kp + k, kp),
        ("mask.astype", kp, kp),
        ("trimmed = tau * mask", 2 * kp, kp),
        ("sum(trimmed, axis=0)", kp, p),
        ("elected = sign(..)", p, p),
        ("sign(trimmed)", kp, kp),
        ("== elected", kp + p, kp),
        ("trimmed != 0", kp, kp),
        ("& (agree)", 2 * kp, kp),
        ("agree.astype", kp, kp),
        ("cnt = sum(agree, axis=0)", kp, p),
        ("maximum(cnt, 1)", p, p),
        ("trimmed * agree", 2 * kp, kp),
        ("sum(.., axis=0)", kp, p),
        ("merged / cnt", 2 * p, p),
        ("b + merged", 2 * p, p),
    ]


def ties_hist_eager_traffic(k: int, p: int, bins: int = 512) -> Dict:
    rows = ties_hist_eager_ops(k, p, bins)
    elems = sum(r + w for _, r, w in rows)
    # "passes": full sweeps over the [k, p] stack equivalent
    return {"elems": elems, "bytes": elems * ELEM,
            "passes": elems / (k * p + p), "ops": len(rows)}


def quant_traffic(k: int, p: int) -> Dict:
    """int8 merge-on-arrival vs dequantize-then-merge, in bytes.

    Fused (kernels/quant.py): read k*p int8 + p*4 base, write p*4 —
    the k*p*4-byte fp32 dequantized stack never exists in HBM.
    Dense path: a dequantize pass (read k*p int8, write k*p*4) then the
    merge pass re-reads those k*p*4 bytes. The avoided round-trip is
    exactly 2*k*p*4 bytes.
    """
    fused = k * p * 1 + p * ELEM + p * ELEM
    dense = (k * p * 1 + k * p * ELEM) + (k * p * ELEM + 2 * p * ELEM)
    return {"fused_bytes": fused, "dense_bytes": dense,
            "fp32_roundtrip_bytes_avoided": 2 * k * p * ELEM,
            "fused_bound_s": bandwidth_bound_s(fused),
            "dense_bound_s": bandwidth_bound_s(dense)}


# --------------------------------------------------------------- gates ---


def _mk(rng, k, lengths):
    leaves = [jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
              for n in lengths]
    bases = [jnp.asarray(rng.standard_normal(n), jnp.float32)
             for n in lengths]
    return leaves, bases


def gates(quick: bool = True) -> List[Dict]:
    """Run every CI gate; returns one dict per gate with ``ok``."""
    from repro.kernels.config import kernel_env
    out: List[Dict] = []
    k, bins = 4, kernel_env.hist_bins
    p = 2 ** 14 if quick else 2 ** 20

    # --- gate 1: fused TIES moves >= 3x fewer HBM bytes than eager ----
    fused = ties_hist_fused_traffic(k, p, bins)
    eager = ties_hist_eager_traffic(k, p, bins)
    ratio = eager["bytes"] / fused["bytes"]
    out.append({
        "gate": "ties_hist_traffic_ratio", "ok": ratio >= TIES_GATE_RATIO,
        "value": ratio, "threshold": TIES_GATE_RATIO,
        "fused": fused, "eager": eager,
        "eager_ops": [{"op": o, "read": r, "write": w}
                      for o, r, w in ties_hist_eager_ops(k, p, bins)],
        "fused_bound_s": bandwidth_bound_s(fused["bytes"]),
        "eager_bound_s": bandwidth_bound_s(eager["bytes"]),
    })
    # the ratio is size-independent in the large-p limit; also check the
    # worst case k=1 so a traffic regression can't hide behind large k
    r1 = (ties_hist_eager_traffic(1, p, bins)["bytes"]
          / ties_hist_fused_traffic(1, p, bins)["bytes"])
    out.append({"gate": "ties_hist_traffic_ratio_k1",
                "ok": r1 >= TIES_GATE_RATIO, "value": r1,
                "threshold": TIES_GATE_RATIO})

    # --- gate 2: batched TIES byte-identical to per-leaf reference ----
    rng = np.random.default_rng(0)
    lengths = [100, 2048, 2049]
    leaves, bases = _mk(rng, k, lengths)
    outs = ops.ties_batch_merge(leaves, bases, 0.2, interpret=True)
    # oracle layout (see ref.ties_hist_ref docstring): threshold from
    # the unpadded row — eager, NOT jitted, since jit constant-folds
    # the cdf's /n into a reciprocal multiply and can shift a
    # borderline bucket — then the merge jitted, like the interpret-
    # mode kernel body, on the block-padded layout the kernel sees
    block = kernel_env.block
    ident = True
    jties = jax.jit(ref.ties_ref)
    for o, s, b, n in zip(outs, leaves, bases, lengths):
        thr = ref.hist_threshold_ref(s, b[None, :], 0.2, bins)
        sp, _ = pad_stacked(s, block)
        bp, _ = pad_flat(b, block)
        r = jties(sp, bp[None, :], thr).reshape(-1)[:n]
        ident &= bool(np.array_equal(np.asarray(o), np.asarray(r)))
    out.append({"gate": "ties_hist_byte_identity", "ok": ident,
                "value": float(ident), "threshold": 1.0,
                "lengths": lengths})

    # --- gate 3: batched DARE bitwise == per-leaf kernel dispatch -----
    seeds = [11 + i for i in range(len(lengths))]
    douts = ops.dare_batch_merge(leaves, bases, seeds, 0.5,
                                 interpret=True)
    block = kernel_env.block
    dident = True
    for o, s, b, n, sd in zip(douts, leaves, bases, lengths, seeds):
        sp, _ = pad_stacked(s, block)
        bp, _ = pad_flat(b, block)
        r = dare_pallas(sp, bp[None, :], jnp.asarray([[sd]], jnp.uint32),
                        p=0.5, block=block, interpret=True)
        dident &= np.array_equal(np.asarray(o),
                                 np.asarray(r).reshape(-1)[:n])
    out.append({"gate": "dare_batch_byte_identity", "ok": bool(dident),
                "value": float(dident), "threshold": 1.0})

    # --- gate 4: int8 merge-on-arrival, zero fp32 dequant round-trips -
    qt = quant_traffic(k, p)
    qs = [jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
          for n in lengths]
    scales = [jnp.asarray(rng.random(k) * 0.01 + 1e-4, jnp.float32)
              for _ in lengths]
    w = jnp.asarray(rng.random(k), jnp.float32)
    qouts = ops.quant_batch_merge(qs, scales, bases, w, interpret=True)
    jref = jax.jit(ref.quant_nary_ref)     # jitted: FMA matches the tile
    qident = True
    for o, q, sc, b, n in zip(qouts, qs, scales, bases, lengths):
        qp, _ = pad_stacked_raw(q, block)
        bp, _ = pad_flat(b, block)
        r = jref(qp, sc, bp[None, :], w.reshape(-1, 1))
        qident &= bool(np.array_equal(np.asarray(o),
                                      np.asarray(r).reshape(-1)[:n]))
    # engine-level: quantized contributions must merge without EVER
    # densifying a leaf (dequant_leaves counter stays zero)
    from repro.core import engine
    from repro.core.compression import compress_tree
    rng2 = np.random.default_rng(7)
    trees = [{"a": jnp.asarray(rng2.standard_normal((8, 33)), jnp.float32),
              "b": jnp.asarray(rng2.standard_normal(257), jnp.float32)}
             for _ in range(3)]
    cts = [compress_tree(t) for t in trees]
    cache = engine.EngineCache()
    plan = engine.plan_merge([engine.contrib_meta(c) for c in cts],
                             "weight_average")
    engine.execute_plan(plan, cts, use_cache=False, pallas=True,
                        max_batch_bytes=1 << 20, cache=cache)
    dequants = int(cache.stats["dequant_leaves"])
    qleaves = int(
        cache.obs.counter("engine_quant_leaves_merged_total").value())
    out.append({
        "gate": "quant_zero_fp32_roundtrips",
        "ok": qident and dequants == 0 and qleaves > 0,
        "value": float(dequants), "threshold": 0.0,
        "byte_identity": qident, "engine_dequant_leaves": dequants,
        "engine_quant_leaves_merged_total": qleaves, "traffic": qt,
    })
    return out


# ---------------------------------------------------------------- rows ---


def _timeit(fn, reps=3) -> float:
    r = fn()
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps * 1e6


def main(quick: bool = True) -> List[Row]:
    from repro.strategies import get_strategy
    rows: List[Row] = []
    k = 4
    sizes = [2 ** 14] if quick else [2 ** 14, 2 ** 20]
    rng = np.random.default_rng(0)
    for p in sizes:
        side = int(np.sqrt(p))
        contribs = [jnp.asarray(rng.standard_normal((side, side)),
                                jnp.float32) for _ in range(k)]
        base = jnp.asarray(rng.standard_normal((side, side)) * 0.1,
                           jnp.float32)
        cat_ties = jax.jit(lambda *c: get_strategy("ties")(list(c),
                                                           base=base))
        us_eager = _timeit(lambda: cat_ties(*contribs))
        us_kern = _timeit(
            lambda: ops.ties_merge(contribs, base, interpret=True))
        rows.append((f"ties_eager_p{p}", us_eager, "jnp_pipeline"))
        fused = ties_hist_fused_traffic(k, p)
        eager = ties_hist_eager_traffic(k, p)
        rows.append((
            f"ties_pallas_interp_p{p}", us_kern,
            f"fused_bytes={fused['bytes']};eager_bytes={eager['bytes']};"
            f"traffic_ratio={eager['bytes'] / fused['bytes']:.2f};"
            f"passes={fused['passes']};interpret=True"))

        us_dare = _timeit(
            lambda: ops.dare_merge(contribs, base, seed=1,
                                   interpret=True))
        rows.append((f"dare_pallas_interp_p{p}", us_dare,
                     "rng_in_kernel;mask_never_in_HBM"))

        us_wa = _timeit(
            lambda: ops.weight_average_merge(contribs, interpret=True))
        rows.append((f"nary_accum_interp_p{p}", us_wa,
                     f"k={k};single_pass"))

        us_sl = _timeit(
            lambda: ops.slerp_merge(contribs[0], contribs[1],
                                    interpret=True))
        rows.append((f"slerp_interp_p{p}", us_sl, "two_pass"))

        qt = quant_traffic(k, p)
        qc = [jnp.asarray(rng.integers(-127, 128, (k, p)), jnp.int8)]
        sc = [jnp.asarray(rng.random(k) * 0.01, jnp.float32)]
        bb = [jnp.asarray(rng.standard_normal(p), jnp.float32)]
        ww = jnp.asarray(rng.random(k), jnp.float32)
        us_q = _timeit(lambda: ops.quant_batch_merge(
            qc, sc, bb, ww, interpret=True))
        rows.append((
            f"quant_nary_interp_p{p}", us_q,
            f"fused_bytes={qt['fused_bytes']};"
            f"dense_bytes={qt['dense_bytes']};"
            f"fp32_roundtrip_avoided={qt['fp32_roundtrip_bytes_avoided']}"
        ))
    for g in gates(quick=quick):
        rows.append((f"gate_{g['gate']}", g["value"],
                     f"ok={g['ok']};threshold={g['threshold']}"))
    return rows


def _cli() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default="",
                    help="write gate results as JSON to this path")
    args = ap.parse_args()
    results = gates(quick=not args.full)
    ok = all(g["ok"] for g in results)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"ok": ok, "hbm_bw": HBM_BW, "gates": results},
                      f, indent=2, default=float)
    for g in results:
        status = "PASS" if g["ok"] else "FAIL"
        print(f"{status} {g['gate']}: value={g['value']:.3f} "
              f"threshold={g['threshold']}")
    if not ok:
        print("bench_kernels: GATE FAILURE", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(_cli())
