"""Work counts against hand counts at side 40 / D 784, and the peak table."""
from __future__ import annotations

import pytest

from harness import peaks, work

N, D, B = 1600, 784, 16


def test_step_flops_hand_count():
    # 16 samples x 1600 units x 784 features x 2, plus 3 D per adaptation
    assert work.step_flops(N, D, B, 0) == 40_140_800 + 37_632
    # each broadcast receipt adds 3 D
    assert work.step_flops(N, D, B, 10) - work.step_flops(N, D, B, 0) == \
        3 * D * 10


def test_step_bytes_hand_count():
    # W in and out (2 x 5,017,600), 16 samples, counters in and out
    assert work.step_bytes(N, D, B, 1) == 10_035_200 + 50_176 + 12_800
    assert work.step_bytes(N, D, B, 4000) == 4000 * 10_098_176


def test_bmu_counts():
    assert work.bmu_flops(B, N, D) == 40_140_800
    assert work.bmu_bytes(1, B, N, D) == 5_017_600 + 16 * (3136 + 8)


def test_least_time_says_which_bound():
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = work.least_time(work.step_flops(N, D, B, 0),
                               work.step_bytes(N, D, B, 1), p)
    assert bound == "memory" and t == pytest.approx(10_098_176 / 819e9)
    t, bound = work.least_time(1e15, 1.0, p)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)


def test_peak_table_refuses_an_unknown_kind():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peak table entry"):
        peaks.peaks_for("TPU v9 imaginary")
