"""Decide work, roofline arithmetic and the peaks table, by hand."""
import pytest

from bench import peaks, work


def test_decide_flops_and_bytes_by_hand():
    # two tasks of 3 and 5 SVs, 4 features, 2 rows
    assert work.decide_flops(2, [3, 5], 4) == 2 * 2 * 4 * (3 + 5)
    bank = (3 * 4 + 3 + 1) + (5 * 4 + 5 + 1)
    assert work.decide_bytes(2, [3, 5], 4) == 4 * (bank + 2 * 4 + 2 * 2)
    assert work.decide_bytes(2, [3, 5], 4, 3) == 4 * (3 * bank + 2 * 4 + 2 * 2)


def test_least_time_is_the_larger_bound():
    peak = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(1000.0, 5.0, peak) == 10.0
    assert work.least_seconds(10.0, 50.0, peak) == 5.0


def test_peaks_of_v5e_and_unknown_kinds():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("cpu")
