"""Least work of one merge round, and the chip peaks it is held to.

A round must at least read the arriving contribution once and write
each merged leaf that the round changed once, on each replica that
resolves; a merged leaf changes where the arriving contribution carries
it (every leaf, for a dense one). It must also do at least one operation per changed
element. Whatever implements the merge, it cannot do less, so a share
of peak computed from this count cannot pass 100% unless the time is
wrong. For these merges the byte term is the larger one.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    """The peaks of one chip kind; a kind missing from the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; add them with their source")
    return table[device_kind]


def round_work(params: int, itemsize: int, replicas: int = 1) -> dict:
    """Operations and HBM bytes one round needs at least, where the
    arriving contribution carries `params` elements (so the round
    changes as many merged ones) and each of `replicas` writes its
    merged model."""
    return {"flops": replicas * params,
            "bytes": (1 + replicas) * params * itemsize}


def least_seconds(work: dict, peaks: dict) -> float:
    return max(work["flops"] / peaks["bf16_flops_per_s"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
