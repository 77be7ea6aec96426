"""The whole round's share of the chip's peak: the least time any
implementation could take for the window's rounds (`work.round_work`
against the peaks of `peaks.json`), summed, over the window's time, in
%. The byte term bounds it for these merges."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from work import least_seconds, round_work  # noqa: E402


def read(run):
    if not run["rounds"] or run.get("peaks") is None:
        return None
    least = least_seconds(round_work(run["round_params"], run["itemsize"],
                                     run["replicas"]), run["peaks"])
    return 100.0 * len(run["rounds"]) * least / run["window_s"]
