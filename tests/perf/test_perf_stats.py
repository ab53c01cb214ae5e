"""The segment median, the percentiles and the interval arithmetic on
hand-made series."""

import pytest

from perf.lib import stats


def test_percentiles_of_a_hand_made_series():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 0) == 10
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.median([3, 1, 2, 10]) == 2.5
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_a_stall_lowers_the_window_rate_and_not_the_median():
    steady = [2.0] * 15
    r = stats.segment_rates(steady, 1536, 30.0)
    assert r["median_rate"] == r["window_rate"] == 768.0
    assert r["slowest_pct"] == 0.0
    stalled = [2.0] * 14 + [3.0]  # one second lost inside one segment
    r = stats.segment_rates(stalled, 1536, 31.0)
    assert r["window_rate"] == pytest.approx(1536 * 15 / 31.0)  # the cell's
    assert r["median_rate"] == 768.0     # the steady phases, beside it
    assert r["slowest_pct"] == pytest.approx(50.0)


def test_time_between_segments_is_paid_too():
    r = stats.segment_rates([2.0] * 15, 1536, 32.0)  # 2 s between calls
    assert r["window_rate"] == pytest.approx(1536 * 15 / 32.0)
    assert r["median_rate"] == 768.0
    with pytest.raises(ValueError):
        stats.segment_rates([2.0] * 15, 1536, 29.0)


def test_a_slow_half_is_seen_by_the_median():
    r = stats.segment_rates([2.0] * 7 + [3.0] * 8, 100, 38.0)
    assert r["median_rate"] == pytest.approx(100 / 3.0)


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert stats.union_seconds(iv) == pytest.approx(3.0)
    assert stats.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert stats.gaps([], 1.0, 2.0) == [(1.0, 2.0)]
    assert stats.gaps([(0.0, 9.0)], 1.0, 2.0) == []
