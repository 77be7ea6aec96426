"""The exact trim quantile: counting selection against `jnp.quantile`.

Times `strategies.select.exact_quantile_rows(a, q)` and
`jnp.quantile(a, q, axis=1, keepdims=True)` on one `[rows, n]` array of
|N(0, std)| values made on the device from `--seed`, each warmed up
first and waited for with `block_until_ready`, and checks that the two
results are bitwise equal (exit 1 if not). The default shape is the
embedding leaf of the `mamba2.ties_window` cell (50288 x 1536, k = 3).

Usage: PYTHONPATH=src python benchmarks/bench_exact_quantile.py
           [--rows 3] [--n 77242368] [--dtype bfloat16] [--q 0.2]
           [--reps 5] [--seed 0] [--std 0.002]

Prints one JSON line: the device, the shape, the median and least
milliseconds of each, and `equal`.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.strategies.select import exact_quantile_rows, quantile_path


def _times(fn, reps):
    jax.block_until_ready(fn())                  # compile + warm
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        out.append((time.perf_counter() - t0) * 1e3)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=3)
    ap.add_argument("--n", type=int, default=77242368)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--q", type=float, default=0.2)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--std", type=float, default=0.002)
    args = ap.parse_args(argv)

    dtype = jnp.dtype(args.dtype)
    key = jax.random.PRNGKey(args.seed & 0x7FFFFFFF)
    a = jnp.abs(jax.random.normal(key, (args.rows, args.n), jnp.float32)
                * args.std).astype(dtype)
    select_ms = _times(lambda: exact_quantile_rows(a, args.q), args.reps)
    sort_ms = _times(
        lambda: jnp.quantile(a, args.q, axis=1, keepdims=True), args.reps)
    got = np.asarray(exact_quantile_rows(a, args.q))
    want = np.asarray(jnp.quantile(a, args.q, axis=1, keepdims=True))
    equal = got.tobytes() == want.tobytes()
    dev = jax.devices()[0]
    print(json.dumps({
        "device": dev.platform, "device_kind": dev.device_kind,
        "shape": [args.rows, args.n], "dtype": dtype.name, "q": args.q,
        "path": quantile_path(dtype, args.n),
        "select_ms_median": statistics.median(select_ms),
        "select_ms_min": min(select_ms),
        "sort_ms_median": statistics.median(sort_ms),
        "sort_ms_min": min(sort_ms),
        "equal": equal, "value": got.astype(np.float32).ravel().tolist()}))
    return 0 if equal else 1


if __name__ == "__main__":
    sys.exit(main())
