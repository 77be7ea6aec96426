"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found
by name from `BENCHMARK.json` at the root of the checkout. With
`--trace 0` the last line of standard output reports the cell's
end-to-end metrics, with `--trace 1` its per-layer metrics read from a
profiled window. The numbers the correctness check compared are the last
lines of standard error, each beside its limit. A machine without a TPU,
with fewer chips than the cell asks for, or with Pallas in interpret
mode, exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402

sys.path.insert(0, str(harness.ROOT / "src"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.load_cell(args.workload, bench)

    print(f"compile cache: {harness.place_compile_cache()}", flush=True)

    rec = harness.run_cell(cell, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START,
                           log=lambda s: print(s, flush=True))
    metrics = {}
    for m in harness.metrics_for(bench, args.workload, bool(args.trace)):
        value = harness.load_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(rec["device"], memory_peak_bytes=rec["memory_peak_bytes"])
    result = {"correct": rec["correct"], "attempted": len(rec["rounds"]),
              "failed": rec["failed"], "metrics": metrics, "device": device}
    if args.trace:
        if rec["trace"] is None:
            raise SystemExit("the trace holds no device operations")
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = {k: rec["trace"][k]
                               for k in ("device_ops", "idle_gaps")}
    result["checks"] = rec["checks"]
    for name, c in rec["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
