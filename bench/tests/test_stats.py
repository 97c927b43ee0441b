"""Rates over the whole window and tails from due time, on synthetic
records."""

import statistics

import pytest

from bench import stats


def test_rate_is_all_work_over_the_whole_window():
    # three steps of 100 tokens end at 0.9, 2.1 and 2.9 s after the start
    assert stats.rate(300, 10.0, 12.9) == pytest.approx(300 / 2.9)
    with pytest.raises(ValueError):
        stats.rate(1, 5.0, 5.0)


def test_ttft_counts_the_wait_from_the_due_time():
    # the generator submitted the second request 0.3 s late, behind a
    # stall: its TTFT counts the stall
    due = [0.0, 1.0, 2.0]
    first = [0.2, 1.6, 2.2]
    assert stats.ttfts_from_due(due, first) == pytest.approx([0.2, 0.6, 0.2])


def test_tail_is_over_every_sample_not_medians_of_pieces():
    slow = [1.0] * 10
    fast = [0.1] * 90
    xs = slow + fast
    assert stats.percentile(xs, 95) == pytest.approx(1.0)
    # the median of per-request p95s would hide the slow tenth
    assert statistics.median([stats.percentile(slow, 95),
                              stats.percentile(fast, 95)]) == \
        pytest.approx(0.55)
    assert stats.percentile([0.0, 10.0], 90) == pytest.approx(9.0)


def test_gaps_of_one_request():
    assert stats.gaps([1.0, 1.5, 2.25]) == pytest.approx([0.5, 0.75])
    assert stats.gaps([3.0]) == []


def test_spread_uses_python_quartiles():
    xs = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / med)
