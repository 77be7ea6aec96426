"""Paper Tables 2/3/4: algebraic property audits.

Tier 1: 4x4 controlled tensors (exact paper setting: seed 42, tol 1e-5).
Tier 2: synthetic production-shape weights (128^2 slices with a 512^2
cross-resolution check — HuggingFace weights are unavailable offline;
see DESIGN.md §9).
"""
from __future__ import annotations

import time
from typing import List, Tuple

import jax

from repro.core.properties import (
    audit_all_raw, audit_all_wrapped, controlled_tensors, production_slices,
    TABLE3_EXPECTED)

Row = Tuple[str, float, str]


def table3_tier1_raw(quick: bool = False) -> List[Row]:
    with jax.enable_x64(True):
        tensors = controlled_tensors(9)
        t0 = time.perf_counter()
        res = audit_all_raw(tensors)
        dt = (time.perf_counter() - t0) * 1e6 / len(res)
    c = sum(r.commutative for r in res.values())
    a = sum(r.associative for r in res.values())
    i = sum(r.idempotent for r in res.values())
    full = sum(r.crdt for r in res.values())
    match = sum((r.commutative, r.associative, r.idempotent)
                == TABLE3_EXPECTED[n] for n, r in res.items())
    return [("table3_tier1_raw", dt,
             f"C={c}/26;A={a}/26;I={i}/26;CRDT={full}/26;"
             f"match_paper={match}/26")]


def table4_tier1_wrapped(quick: bool = False) -> List[Row]:
    with jax.enable_x64(True):
        tensors = controlled_tensors(9)
        t0 = time.perf_counter()
        res = audit_all_wrapped(tensors)
        dt = (time.perf_counter() - t0) * 1e6 / len(res)
    total = sum(r.commutative + r.associative + r.idempotent + r.convergent
                for r in res.values())
    return [("table4_tier1_wrapped", dt, f"pass={total}/104")]


def table1_tier2_production(quick: bool = False) -> List[Row]:
    from repro.configs import get_config
    rows: List[Row] = []
    dims = (128,) if quick else (128, 512)
    for dim in dims:
        base, tensors = production_slices(get_config("minitron-8b"), n=9,
                                          slice_dim=dim)
        t0 = time.perf_counter()
        raw = audit_all_raw(tensors, base=base)
        wrapped = audit_all_wrapped(tensors, base=base)
        dt = (time.perf_counter() - t0) * 1e6 / (2 * len(raw))
        c = sum(r.commutative for r in raw.values())
        a = sum(r.associative for r in raw.values())
        i = sum(r.idempotent for r in raw.values())
        wp = sum(r.crdt for r in wrapped.values())
        rows.append((f"table1_tier2_{dim}x{dim}", dt,
                     f"raw:C={c}/26;A={a}/26;I={i}/26|wrapped={wp}/26"))
    return rows


def main(quick: bool = True) -> List[Row]:
    rows = []
    rows += table3_tier1_raw(quick)
    rows += table4_tier1_wrapped(quick)
    rows += table1_tier2_production(quick)
    return rows


if __name__ == "__main__":
    for r in main(quick=False):
        print(",".join(str(x) for x in r))
