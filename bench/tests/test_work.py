"""The compulsory-work count and the peak table."""
import pytest

import work


def test_three_modes_by_hand():
    # nnz 100, shape (10, 20, 30), R 4. Per mode: 2*3*4 FLOP per nonzero;
    # stream 100*(3+1)*4 = 1600 B, factors (10+20+30)*4*4 = 960 B read and
    # written across the mode; per remap 2*1600 B.
    flops, nbytes = work.sweep_work(100, (10, 20, 30), 4)
    assert flops == 3 * (2 * 3 * 4 * 100)
    assert nbytes == 3 * (1600 + 960) + 3 * 2 * 1600


def test_four_modes_by_hand():
    # nnz 10, shape (2, 3, 4, 5), R 2: stream 10*5*4 = 200 B, factors
    # 14*2*4 = 112 B, FLOP 2*4*2 = 16 per nonzero per mode.
    flops, nbytes = work.sweep_work(10, (2, 3, 4, 5), 2)
    assert flops == 4 * 16 * 10
    assert nbytes == 4 * (200 + 112) + 4 * 2 * 200


def test_nell2_is_bandwidth_bound():
    flops, nbytes = work.sweep_work(12_000_000, (12092, 9184, 28818), 16)
    assert nbytes == pytest.approx(1.7376e9, rel=1e-3)
    least = work.sweep_least_seconds(12_000_000, (12092, 9184, 28818), 16,
                                     "TPU v5 lite")
    assert least == pytest.approx(nbytes / 819e9)
    assert least > flops / 197e12
    four = work.sweep_least_seconds(12_000_000, (12092, 9184, 28818), 16,
                                    "TPU v5 lite", chips=4)
    assert four == pytest.approx(least / 4)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        work.sweep_least_seconds(10, (2, 3), 2, "cpu")
