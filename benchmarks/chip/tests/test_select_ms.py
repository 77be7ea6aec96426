"""`metrics/select_ms.py` on a recorded `device_ops` list, and without
one."""
import pytest

import harness

READ = harness.load_reader("select_ms")

# the `device_ops` of a traced mamba2.ties_window run on a TPU v5e
# (19 rounds), as `trace_reduce.reduce_trace` reports them
RECORDED_OPS = [["jit__select_quantile", 0.808511253],
                ["jit_reshape", 0.275786625],
                ["jit__reduce_sum", 0.24902815500000003],
                ["jit_multiply", 0.18815610300000002],
                ["jit_equal", 0.11284674700000001],
                ["jit_sign", 0.10411403500000001],
                ["jit_convert_element_type", 0.101659789],
                ["jit_gen_contribution", 0.085006788],
                ["jit_abs", 0.06317107400000001],
                ["jit_subtract", 0.054421535]]


def _run(device_ops, rounds=19):
    trace = None if device_ops is None else {
        "busy_s": 2.34, "window_s": 52.14, "device_ops": device_ops,
        "idle_gaps": []}
    return {"trace": trace, "rounds": [{"round_s": 2.7}] * rounds}


def test_reads_the_selection_per_round():
    assert READ(_run(RECORDED_OPS)) == pytest.approx(808.511253 / 19)


def test_absent_without_a_trace_or_without_the_selection():
    assert READ(_run(None)) is None
    assert READ(_run(RECORDED_OPS[1:])) is None
    assert READ(_run(RECORDED_OPS, rounds=0)) is None
