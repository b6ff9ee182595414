import pytest

from perfbench.harness import stats


@pytest.mark.parametrize("q, want", [(50, 5), (95, 10), (100, 10), (10, 1), (1, 1)])
def test_percentile_is_nearest_rank_over_all_values(q, want):
    assert stats.percentile(list(range(10, 0, -1)), q) == want


def test_percentile_counts_a_miss_as_the_sentinel():
    # 1 of 10 never committed: the 95th percentile is the miss
    assert stats.percentile([1.0] * 9 + [60000.0], 95) == 60000.0
    assert stats.percentile([1.0] * 9 + [60000.0], 50) == 1.0


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_counts_every_commit_of_the_window_over_all_its_seconds():
    times = [0.5, 1.0, 1.5, 2.0, 2.999, 3.0, 7.0]
    assert stats.rate_in_window(times, 1.0, 3.0) == 4 / 2.0  # [1, 3): 3.0 is out
    with pytest.raises(ValueError):
        stats.rate_in_window(times, 3.0, 3.0)


def test_max_gap_counts_the_edges():
    assert stats.max_gap([1.2, 1.3, 2.9], 1.0, 3.0) == pytest.approx(1.6)
    assert stats.max_gap([], 1.0, 3.0) == 2.0
    assert stats.max_gap([2.5], 1.0, 3.0) == 1.5


def test_spread_is_the_interquartile_distance_over_the_median():
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    import statistics

    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / 102.5)


def test_served_outcomes_a_refusal_fails_the_tx_but_not_the_guarantee():
    from perfbench.harness import cells, drive

    served = cells.kind("served")
    ms = 1_000_000
    offsets = [i * 10 * ms for i in range(6)]
    reply = {
        #          lead    ok          refused  never       wrong code
        "status": [0, 0, 0, 429, 0, 0],
        "sent_ns": [0, 10 * ms, 20 * ms + 50_000, 31 * ms, 40 * ms, 50 * ms],
        "event_ns": [5 * ms, 19 * ms, 32 * ms, 0, 0, 61 * ms],
        "event_code": [0, 0, 0, -1, -1, 1],
    }
    out = served.served_outcomes(reply, 0, offsets, range(1, 6))
    assert out["lat_ms"] == [9.0, 12.0, drive.NEVER_MS, drive.NEVER_MS, 11.0]
    assert out["late_ms"] == [0.0, 0.05, 1.0, 0.0, 0.0]
    assert out["refused"] == [3] and out["never"] == [4]
    assert out["acknowledged"] == [1, 2, 4, 5] and out["event_wrong"] == 1
