"""CLI: CRDT-merge trained checkpoints.

  PYTHONPATH=src python -m repro.launch.merge \
      --arch minitron-8b --smoke --strategy ties \
      --inputs /tmp/ck_a/step_00000010 /tmp/ck_b/step_00000010 \
      --base /tmp/ck_base/step_00000000 --out /tmp/merged

Every input checkpoint becomes one OR-Set contribution; the resolve is
deterministic in the contribution SET (order/duplication of --inputs is
irrelevant by construction — the point of the paper).

Output goes through the `repro.obs` structured event log: the default
verbosity prints exactly the legacy lines, `--verbose` prints the JSON
events instead, `--quiet` prints nothing, and `--events-out FILE`
additionally dumps the full event stream as JSONL regardless of
verbosity.
"""
from __future__ import annotations

import argparse

import jax

from repro.api import MergeSpec, Replica
from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.configs import get_config, smoke_config
from repro.core.resolve import seed_from_root
from repro.launch.compile_cache import place_compile_cache
from repro.models.model import Model
from repro.obs import EventLog
from repro.train.step import init_train_state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--strategy", default="ties")
    ap.add_argument("--inputs", nargs="+", required=True)
    ap.add_argument("--base", default="",
                    help="base checkpoint for task-vector strategies")
    ap.add_argument("--out", required=True)
    ap.add_argument("--node", default="merge-cli")
    ap.add_argument("--state-dir", default="",
                    help="durable replica directory: contributions are "
                    "journaled (crash-safe) and a re-run resumes from "
                    "the recovered OR-Set instead of starting empty")
    vb = ap.add_mutually_exclusive_group()
    vb.add_argument("--quiet", action="store_true",
                    help="no stdout output")
    vb.add_argument("--verbose", action="store_true",
                    help="print structured JSON events instead of text")
    ap.add_argument("--events-out", default="",
                    help="also write the event stream to this JSONL file")
    args = ap.parse_args()
    place_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg)
    like = init_train_state(model, jax.random.PRNGKey(0))

    replica = Replica(args.node, path=args.state_dir or None)
    log = EventLog.from_args(args, registry=replica.obs)
    if args.state_dir and replica.visible():
        log.emit("state_recovered",
                 f"recovered {len(replica.visible())} contributions from "
                 f"{args.state_dir} "
                 f"(root {replica.merkle_root().hex()[:16]}…)",
                 state_dir=args.state_dir,
                 visible=len(replica.visible()),
                 root=replica.merkle_root().hex())
    for path in args.inputs:
        ckpt, meta = restore_checkpoint(path, like)
        eid = replica.contribute(ckpt["params"])
        log.emit("contribution_added",
                 f"added {path} (data_step={meta.get('data_step')}) "
                 f"visible={len(replica.visible())}",
                 path=path, eid=eid,
                 data_step=meta.get("data_step"),
                 visible=len(replica.visible()))

    base = None
    if args.base:
        base_ckpt, _ = restore_checkpoint(args.base, like)
        base = base_ckpt["params"]

    merged = replica.resolve(MergeSpec(args.strategy), base=base)
    root = replica.merkle_root()
    log.emit("resolved",
             f"resolved {len(replica.visible())} contributions with "
             f"{args.strategy} (root {root.hex()[:16]}…, "
             f"seed {seed_from_root(root)})",
             strategy=args.strategy, k=len(replica.visible()),
             root=root.hex(), seed=seed_from_root(root))

    out_state = dict(like)
    out_state["params"] = merged
    path = save_checkpoint(args.out, out_state, 0,
                           metadata={"merged_from": args.inputs,
                                     "strategy": args.strategy,
                                     "merkle_root": root.hex(),
                                     "data_step": 0})
    log.emit("checkpoint_written",
             f"wrote merged checkpoint to {path}", path=str(path))
    replica.close()
    if args.events_out:
        log.dump(args.events_out)


if __name__ == "__main__":
    main()
