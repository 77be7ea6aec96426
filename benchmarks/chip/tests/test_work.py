"""The least work of a round, the peaks table, and `round_mfu`."""
import pytest

import harness
import work


def test_round_work_counts_one_read_and_one_write():
    assert work.round_work(310_256_640, 2) == {
        "flops": 310_256_640, "bytes": 4 * 310_256_640}


def test_round_work_counts_a_write_on_each_replica():
    assert work.round_work(1000, 2, replicas=2) == {
        "flops": 2000, "bytes": 6000}


def test_bytes_bound_a_round_on_v5e():
    peaks = work.peaks_for("TPU v5 lite")
    w = work.round_work(135_808_064, 2)
    assert work.least_seconds(w, peaks) == pytest.approx(
        w["bytes"] / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks_for("TPU v99")


def test_round_mfu_reaches_100_only_at_the_least_time():
    peaks = work.peaks_for("TPU v5 lite")
    least = work.least_seconds(work.round_work(1000, 2), peaks)
    run = {"rounds": [{}] * 4, "round_params": 1000, "itemsize": 2,
           "replicas": 1, "peaks": peaks, "window_s": 4 * least}
    read = harness.load_reader("round_mfu")
    assert read(run) == pytest.approx(100.0)
    run["window_s"] *= 2
    assert read(run) == pytest.approx(50.0)
    assert read(dict(run, peaks=None)) is None
