"""Production-style training CLI.

Single-branch trainer with sharded state, donation, checkpoint/restart and
deterministic data cursors. For the decentralised multi-branch flow see
repro.train.btm (and examples/btm_train.py).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch minitron-8b --smoke \
      --steps 20 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch minitron-8b --smoke \
      --steps 40 --ckpt-dir /tmp/ckpt --resume   # continues from step 20
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import latest_checkpoint, restore_checkpoint, \
    save_checkpoint
from repro.configs import get_config, smoke_config
from repro.data.synthetic import SyntheticTask
from repro.launch.compile_cache import place_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.sharding import policy
from repro.train.step import init_train_state, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1",
                    help="data x model, e.g. 4x2 (device count must match)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--task", type=int, default=0,
                    help="synthetic task id (branch divergence for merging)")
    args = ap.parse_args()
    place_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.replace(grad_accum=max(1, min(cfg.grad_accum, args.batch)))
    model = Model(cfg)
    dshape, mshape = (int(x) for x in args.mesh.split("x"))
    mesh = None
    if dshape * mshape > 1:
        mesh = make_mesh((dshape, mshape), ("data", "model"))
        policy.set_mesh(mesh)

    state = init_train_state(model, jax.random.PRNGKey(0))
    start_step = 0
    if args.resume and args.ckpt_dir:
        path = latest_checkpoint(args.ckpt_dir)
        if path:
            state, meta = restore_checkpoint(path, state)
            start_step = int(meta["data_step"])
            print(f"resumed from {path} at data step {start_step}")

    if mesh is not None:
        shardings = policy.state_shardings(model, mesh, state)
        state = jax.device_put(state, shardings)
    step_fn = jax.jit(make_train_step(model, total_steps=args.steps),
                      donate_argnums=(0,))

    task = SyntheticTask(cfg.vocab_size, args.seq, task_id=args.task)
    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = {"tokens": jnp.asarray(task.batch(step, args.batch))}
        state, mets = step_fn(state, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            print(f"step {step:5d} loss {float(mets['loss']):.4f} "
                  f"gnorm {float(mets['grad_norm']):.3f} "
                  f"({dt:.1f}s)", flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, jax.device_get(state), step + 1,
                            metadata={"data_step": step + 1,
                                      "arch": cfg.name})
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, jax.device_get(state), args.steps,
                        metadata={"data_step": args.steps,
                                  "arch": cfg.name})
    print("done")


if __name__ == "__main__":
    main()
