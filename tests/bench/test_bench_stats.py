"""Percentile, open-loop latency and spread arithmetic of the benchmark."""
import math

import pytest

from benchmarks.chip import stats


def test_percentile_nearest_rank():
    vals = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.percentile(vals, 0) == 1.0
    assert stats.percentile(vals, 50) == 3.0
    assert stats.percentile(vals, 100) == 5.0
    # round(0.95 * 19) = 18: the 19th of 20 sorted values.
    assert stats.percentile(range(1, 21), 95) == 19


def test_missing_answers_count_as_infinite():
    lat = stats.latencies_from_due([0.0, 1.0, 2.0, 3.0], [0.5, None, 2.25, None])
    assert lat[0] == 0.5 and lat[2] == 0.25
    assert math.isinf(lat[1]) and math.isinf(lat[3])
    # Half the sample missing: the median is finite, the tail is not.
    assert stats.percentile(lat, 25) == 0.5
    assert math.isinf(stats.percentile(lat, 95))


def test_latency_runs_from_due_time_not_send_time():
    # Due at 1.0, sent late at 1.4, answered at 1.5: 0.5 s, not 0.1 s.
    assert stats.latencies_from_due([1.0], [1.5]) == [0.5]


@pytest.mark.parametrize("bad", [[], None])
def test_percentile_of_nothing_is_an_error(bad):
    with pytest.raises((ValueError, TypeError)):
        stats.percentile(bad, 50)


def test_latencies_need_one_completion_per_request():
    with pytest.raises(ValueError):
        stats.latencies_from_due([0.0, 1.0], [0.5])

