"""The traffic generator and the tail arithmetic."""

import math
import statistics

import numpy as np
import pytest

from benchmarks.harness import arrivals
from benchmarks.harness.window import percentile


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_generator_is_a_pure_function_of_the_seed(seed):
    a = arrivals.record_pool(30522, 512, 16, seed)
    b = arrivals.record_pool(30522, 512, 16, seed)
    c = arrivals.record_pool(30522, 512, 16, seed + 1)
    assert a.dtype == np.int32 and a.shape == (16, 512)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len({r.tobytes() for r in a}) == 16, "rows all differ"
    d1 = arrivals.poisson_due_times(500.0, 10.0, seed)
    d2 = arrivals.poisson_due_times(500.0, 10.0, seed)
    d3 = arrivals.poisson_due_times(500.0, 10.0, seed + 1)
    assert np.array_equal(d1, d2) and not np.array_equal(d1, d3)


def test_every_seed_gets_the_same_gaps_in_another_order():
    full = [arrivals.poisson_gaps(300.0, 10.0, s) for s in (1, 2)]
    assert np.allclose(np.sort(full[0]), np.sort(full[1]))
    assert not np.allclose(full[0], full[1])


@pytest.mark.parametrize("rate", [50.0, 500.0, 2000.0])
def test_poisson_rate_and_spread(rate):
    due = arrivals.poisson_due_times(rate, 20.0, 3)
    assert np.all(np.diff(due) > 0) and due[-1] < 20.0
    assert abs(len(due) / 20.0 - rate) / rate < 0.05
    gaps = np.diff(due)
    # an exponential's standard deviation equals its mean
    assert abs(np.std(gaps) / np.mean(gaps) - 1.0) < 0.1


def test_latency_runs_from_the_due_time_and_unanswered_counts_as_missed():
    due = [0.0, 0.1, 0.2, 0.3]
    answered = [0.05, 0.35, None, 0.31]
    lat = arrivals.latencies_ms(due, answered)
    assert lat[0] == pytest.approx(50.0) and lat[1] == pytest.approx(250.0)
    assert math.isinf(lat[2]) and lat[3] == pytest.approx(10.0)
    # one unanswered record in four is over any limit at the 95th percentile
    assert math.isinf(percentile(lat, 95))
    assert percentile(lat, 50) == pytest.approx(50.0)


def test_percentile_is_nearest_rank_over_all_values():
    xs = list(range(1, 101))
    assert percentile(xs, 95) == 95 and percentile(xs, 100) == 100
    assert percentile([5.0], 95) == 5.0
    with pytest.raises(ValueError):
        percentile([], 95)


def test_p95_over_all_records_sees_a_stall_a_median_of_chunks_does_not():
    steady = [10.0] * 1000
    stalled = list(steady)
    for i in range(400, 480):               # one stall: 8% of the records
        stalled[i] = 500.0
    assert percentile(steady, 95) == 10.0
    assert percentile(stalled, 95) == 500.0

    def median_of_chunk_p95(xs, chunk=100):
        return statistics.median(
            percentile(xs[k:k + chunk], 95) for k in range(0, len(xs), chunk))

    assert median_of_chunk_p95(stalled) == 10.0
