"""Readings that the correctness limits of a cell are set from.

    python3 benchmarks/chip/readings.py --workload <cell> \\
        --seeds 11,12,... --control-seeds 21,22,23 --seconds 12

In one process, on the chip: for each of `--seeds`, one run of the cell
(set-up, a short window at the cell's own load, the check) prints the
numbers it compared; for each of `--control-seeds`, one run of the cell
with the control in the program's place (`control_resolve`) prints the
same numbers, as the same check reads them. The benchmark's own runs
never run this. Each reading is one JSON line on standard output.
"""
import argparse
import contextlib
import json
import sys
import time
from pathlib import Path
from unittest import mock

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402

sys.path.insert(0, str(harness.ROOT / "src"))


def control_resolve(strategy: str, cfg: dict):
    """A stand-in for `Replica.resolve`: the reference, fed int8 copies
    (`reference.quantize`, the precision below the configuration's
    bfloat16) of the replica's visible contributions and of its pinned
    base, merged in canonical order (ascending element id)."""
    import jax

    import reference

    def resolve(self, spec, **_):
        contribs = self.state.visible_contributions()
        trees = [contribs[e] for e in sorted(contribs)]
        base = self._bases[spec.base_ref] if spec.base_ref else None
        model = base if base is not None else max(
            trees, key=lambda t: len(jax.tree_util.tree_leaves(t)))
        merged = dict(reference.merged_leaves(strategy, trees, base, cfg,
                                              lower=True))
        flat, treedef = jax.tree_util.tree_flatten_with_path(model)
        return jax.tree_util.tree_unflatten(
            treedef, [merged[jax.tree_util.keystr(p)] for p, _ in flat])
    return resolve


def run(cell: dict, seed: int, seconds: float, control: bool,
        check_device: bool = True, log=None) -> dict:
    """One run of `cell`, with the control in the program's place when
    `control` is set."""
    from repro.api import Replica
    traffic = cell["traffic"]
    patch = (mock.patch.object(Replica, "resolve", control_resolve(
                 traffic["strategy"], dict(traffic["cfg"])))
             if control else contextlib.nullcontext())
    with patch:
        return harness.run_cell(
            cell, seed, seconds, False, t_start=time.perf_counter(),
            check_device=check_device,
            log=log or (lambda s: print(s, file=sys.stderr, flush=True)))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)

    harness.place_compile_cache()
    for flag, seeds in ((False, args.seeds), (True, args.control_seeds)):
        for seed in [int(s) for s in seeds.split(",") if s]:
            rec = run(cell, seed, args.seconds, flag)
            print(json.dumps({"seed": seed, "control": flag,
                              "rounds": len(rec["rounds"]),
                              "correct": rec["correct"],
                              "checks": rec["checks"]}), flush=True)


if __name__ == "__main__":
    main()
