"""Operation and byte counts of the admission kernels, and the peaks."""
import pytest

from bench import roofline


def test_rate_prefix_cost_by_hand():
    # one pair, 2 hops, 120 steps: the pair spans 3 hours
    # ops   = 35 * 2 * 120                       = 8,400
    # bytes = 2 * (12 * 120 + 2 * 4 * 3 + 8 * 4) = 2,992
    assert roofline.rate_prefix_cost([(2, 120)]) == (8400.0, 2992.0)
    assert roofline.rate_prefix_cost([(2, 120), (2, 120)]) == (16800.0,
                                                               5984.0)


def test_sweep_cost_by_hand():
    # one cell, legs of 3 and 2 hops, 4 start slots
    # ops   = (7 * 5 + 5 * 2 + 9) * 4                 = 216
    # bytes = 20 * 5 * 4 + 4 * 5 + 4 * 2 * 4 + 8 * 4 = 484
    assert roofline.sweep_cost([((3, 2), 4)]) == (216.0, 484.0)


def test_least_time_names_its_bound():
    t, bound = roofline.least_s(8400.0, 2992.0, "TPU v5 lite")
    assert bound == "memory"
    assert t == pytest.approx(2992.0 / 819e9)
    t, bound = roofline.least_s(1e15, 1.0, "TPU v5 lite")
    assert bound == "compute"
    assert t == pytest.approx(1e15 / 197e12)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("TPU v99")
    with pytest.raises(ValueError):
        roofline.least_s(1.0, 1.0, "cpu")
