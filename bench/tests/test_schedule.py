"""The seeded open-loop schedule and the percentile over all requests."""
import math

import numpy as np
import pytest

from bench import schedule


def test_same_seed_same_schedule():
    a = schedule.poisson(5000.0, 2.0, 2 ** 31 + 9)
    b = schedule.poisson(5000.0, 2.0, 2 ** 31 + 9)
    assert np.array_equal(a, b)


def test_seeds_share_the_work_in_another_order():
    a = schedule.poisson(5000.0, 2.0, 1)
    b = schedule.poisson(5000.0, 2.0, 2)
    assert len(a) == len(b) == 10_000
    assert not np.array_equal(a, b)
    ga, gb = np.diff(a), np.diff(b)
    # the same gaps, but for the one each leaves out at the end
    assert np.sort(np.concatenate([ga, [0]]))[1:].sum() == pytest.approx(
        np.sort(np.concatenate([gb, [0]]))[1:].sum(), rel=1e-3)
    assert a[-1] == pytest.approx(2.0, rel=0.01) and b[-1] == pytest.approx(2.0, rel=0.01)


def test_schedule_is_ascending_from_zero_at_the_rate():
    due = schedule.poisson(1000.0, 10.0, 7)
    assert due[0] == 0.0
    assert np.all(np.diff(due) > 0)
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(1e-3, rel=0.01)
    # exponential gaps: the coefficient of variation is about 1
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, rel=0.1)


def test_percentile_is_nearest_rank_over_all_requests():
    lat = np.arange(1, 101, dtype=float)            # 1..100
    assert schedule.percentile(lat, 95.0) == 95.0
    assert schedule.percentile(lat, 50.0) == 50.0
    assert schedule.percentile(lat, 100.0) == 100.0


def test_missing_requests_count_as_misses():
    lat = np.concatenate([np.full(94, 0.001), np.full(6, np.inf)])
    assert math.isinf(schedule.percentile(lat, 95.0))
    lat = np.concatenate([np.full(95, 0.001), np.full(5, np.inf)])
    assert schedule.percentile(lat, 95.0) == 0.001
