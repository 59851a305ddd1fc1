"""Percentile, rate and spread arithmetic on synthetic samples."""
import pytest

import stats


def test_percentile_is_nearest_rank_and_keeps_every_sample():
    vals = list(range(1, 101))                  # 1..100
    assert stats.percentile(vals, 0.50) == 50
    assert stats.percentile(vals, 0.95) == 95
    assert stats.percentile(vals, 1.0) == 100
    assert stats.percentile([7.0], 0.95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_a_stall_shows_in_the_tail_not_in_the_median():
    # 94 registrations at 100 ms, 6 that sat behind a 5 s stall
    lat = [0.1] * 94 + [5.0] * 6
    s = stats.latency_summary_ms(lat)
    assert s["p50"] == pytest.approx(100.0)
    assert s["p95"] == pytest.approx(5000.0)
    assert s["max"] == pytest.approx(5000.0) and s["n"] == 100


def test_rate_is_all_the_work_over_all_the_time():
    # a stall does not shorten the window a rate is taken over
    assert stats.rate_per_s(6400, 20.0) == 320.0
    with pytest.raises(ValueError):
        stats.rate_per_s(1, 0.0)


def test_union_length_merges_nested_and_overlapping_intervals():
    iv = [(0.0, 10.0), (1.0, 2.0), (9.0, 12.0), (20.0, 21.0)]
    assert stats.union_length(iv) == pytest.approx(13.0)
    assert stats.union_length([]) == 0.0
