"""bytes per solve from shapes: the hand-worked case of the docstring."""

import pytest

from benchmarks.lib import files, solve_work


def test_hand_worked_basic_case():
    # 5,000 nodes x 3 resource words x 4 B = 60,000 B read;
    # 1,024 pods x (16 B read + 4 B assignment + 12 B node columns) = 32,768 B
    assert solve_work.solve_bytes(5000, 1024, 0) == 60_000 + 32_768 == 92_768
    peaks = solve_work.load_peaks("TPU v5 lite")
    cfg = files.load_config("sched-perf-basic-5000n")
    assert solve_work.families(cfg) == 0
    least = solve_work.min_seconds(cfg, solves=1, pods=1024, peaks=peaks)
    assert least == pytest.approx(92_768 / 819e9)
    assert least * 1e6 == pytest.approx(0.11327, rel=1e-3)


def test_spread_case_counts_one_family():
    cfg = files.load_config("sched-perf-spread-5000n")
    assert solve_work.families(cfg) == 1
    # per node 12 + 4 = 16 B; per pod 32 + 4 = 36 B
    assert solve_work.solve_bytes(5000, 1024, 1) == 5000 * 16 + 1024 * 36
    peaks = solve_work.load_peaks("TPU v5 lite")
    two = solve_work.min_seconds(cfg, solves=2, pods=300, peaks=peaks)
    assert two == pytest.approx((2 * 5000 * 16 + 300 * 36) / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        solve_work.load_peaks("TPU v99")
    assert "TPU v5e" in solve_work.load_peaks("TPU v5 lite")["source"]
