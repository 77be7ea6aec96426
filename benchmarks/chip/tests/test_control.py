"""The control comes out not correct: a whole run (set-up, window,
check) on the CPU, with `Replica.resolve` replaced by the reference fed
int8 copies of its inputs (the precision below the configuration's
bfloat16), is judged by the run's own check to lie further from the
reference than each traffic mix's limit allows, by three times or more."""
import pytest

import harness
import readings
from conftest import TINY_CONFIG


@pytest.mark.parametrize("mix", ["wa_window", "ties_window"])
@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_int8_control_fails_the_check(mix, seed):
    cell = {"name": mix, "chips": 1, "config": TINY_CONFIG,
            "traffic": harness.load_traffic(mix)}
    rec = readings.run(cell, seed, 0.3, control=True, check_device=False,
                       log=lambda s: None)
    gap = rec["checks"]["merged_gap"]
    assert rec["rounds"] and rec["correct"] is False
    assert gap["value"] > 3 * gap["limit"]
