"""The peaks table and the step's least time."""

import pytest

from benchmarks import roofline


def test_an_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        roofline.step_floor("TPU v9", 65536, 1, 1, 4)


def test_v5e_peaks_and_the_step_floor():
    peaks = roofline.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    floor = roofline.step_floor("TPU v5 lite", 65536, 1, 1, 4)
    assert floor["bound"] == "bandwidth"
    assert floor["bytes"] == 4.0 * 56 * 65536
    assert floor["seconds"] == pytest.approx(floor["bytes"] / 819e9)
    # twice the rows, twice the floor; capacity is not an argument
    assert roofline.step_floor("TPU v5 lite", 131072, 1, 1, 4)["seconds"] \
        == pytest.approx(2 * floor["seconds"])
