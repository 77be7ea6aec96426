"""Batched serving CLI: prefill a prompt batch, decode greedily.

  PYTHONPATH=src python -m repro.launch.serve --arch whisper-tiny --smoke \
      --batch 4 --prompt-len 16 --gen 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.configs.base import ShapeSpec
from repro.data.synthetic import make_batch
from repro.launch.compile_cache import place_compile_cache
from repro.models.model import Model
from repro.train.serve import greedy_decode


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    args = ap.parse_args()
    place_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    shape = ShapeSpec("serve", args.prompt_len, args.batch, "prefill")
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape).items()}
    t0 = time.time()
    out = greedy_decode(model, params, batch, steps=args.gen)
    dt = time.time() - t0
    toks = args.batch * args.gen
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s incl. compile)")
    print("sample:", np.asarray(out[0]))


if __name__ == "__main__":
    main()
