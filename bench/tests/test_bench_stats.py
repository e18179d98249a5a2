"""Percentile, arrival and window arithmetic of the benchmark."""

import math

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import numpy as np
import pytest

from benchlib import stats


@pytest.mark.parametrize("p,want", [(50, 5), (90, 9), (99, 10), (100, 10), (10, 1), (1, 1)])
def test_nearest_rank_percentile(p, want):
    assert stats.percentile(range(10, 0, -1), p) == want


def test_percentile_puts_missing_last():
    vals = [1.0] * 98 + [stats.MISSING] * 2
    assert stats.percentile(vals, 98) == 1.0
    assert math.isinf(stats.percentile(vals, 99))


def test_percentile_rejects_bad_input():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 0)


def test_poisson_arrivals_fixed_count_in_window():
    a = stats.poisson_arrivals(np.random.default_rng(1), 61.2, 30.0)
    b = stats.poisson_arrivals(np.random.default_rng(2), 61.2, 30.0)
    assert len(a) == len(b) == 1836
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 30.0
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, stats.poisson_arrivals(np.random.default_rng(1), 61.2, 30.0))
    # exponential gaps: mean 1/rate, coefficient of variation near 1
    gaps = np.diff(a)
    assert abs(gaps.mean() * 61.2 - 1) < 0.1 and abs(gaps.std() / gaps.mean() - 1) < 0.15


def test_count_before_and_mean():
    assert stats.count_before([0.1, 0.5, 1.0, 1.5], 1.0) == 2
    assert stats.mean([1, 2, 3, 6]) == 3
    with pytest.raises(ValueError):
        stats.mean([])
