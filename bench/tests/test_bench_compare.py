"""The comparison's arithmetic."""
import math

import numpy as np
import pytest

from bench import compare


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = np.array([1.0, 2.0, 3.0, 1e-9])
    prog = np.array([1.01, 2.0, 3.0, 2e-9])
    # each gap counts against max(own norm, median 1.5): the tiny leaf's
    # doubling is 1e-9 / 1.5, the first leaf's 0.01 / 1.5
    assert compare.worst_leaf_gap(prog, ref) == pytest.approx(0.01 / 1.5)
    assert compare.worst_leaf_gap(prog, ref, keep=ref > 1.5) == 0.0


def test_gaps_of_nothing_and_of_something_from_nothing():
    assert compare.worst_leaf_gap(np.zeros(3), np.zeros(3)) == 0.0
    assert compare.worst_leaf_gap(np.array([0.0, 1e-3]), np.zeros(2)) \
        == math.inf
    assert compare.rel_gap([float("nan")], [1.0]) == math.inf


def test_deviating_share_counts_misses_and_nans():
    ref = [np.zeros((2, 4)), np.ones((2, 4))]
    prog = [np.zeros((2, 4)), np.ones((2, 4))]
    assert compare.deviating_share(prog, ref) == 0.0
    prog[1][0, 0] = 1.5
    prog[0][1, 1] = float("nan")
    assert compare.deviating_share(prog, ref) == 2 / 16


def test_every_number_needs_a_limit_and_a_check_needs_a_finite_value():
    with pytest.raises(KeyError):
        compare.checks({"a": 1.0, "b": 2.0}, {"a": 1.0})
    assert compare.checks({"a": 0.5}, {"a": 1.0}) == [("a", 0.5, 1.0)]
    assert compare.holds(1.0, 1.0) and not compare.holds(math.nan, 1.0)
    assert not compare.holds(1.1, 1.0)


def test_window_stall_is_one_on_a_straight_path_and_grows_as_it_stops():
    # three steps of gradient norm 2 along one line at eta 0.5 move the
    # iterate by 3: the ratio is 1
    assert compare.window_stall(0.5, [2.0, 2.0, 2.0], 3.0) == 1.0
    # the same steps that moved it by a third as far
    assert compare.window_stall(0.5, [2.0, 2.0, 2.0], 1.0) == 3.0
    assert compare.window_stall(0.5, [2.0], 0.0) == math.inf
    assert compare.window_stall(0.5, [float("nan")], 1.0) == math.inf
