"""Rate and percentile arithmetic, with a window that stalls."""
import math

import numpy as np
import pytest

from chipbench import stats, traffic


def test_percentile_matches_numpy_linear():
    v = np.random.default_rng(0).exponential(size=137)
    for q in (50, 90, 99):
        assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_failed_requests_count_as_infinitely_late():
    v = [1.0] * 8 + [math.inf] * 2
    assert stats.percentile(v, 50) == 1.0
    assert stats.percentile(v, 90) == math.inf


def test_closed_loop_rate_counts_the_whole_window():
    steady = [(i * 1.0, (i + 1) * 1.0, 8) for i in range(10)]
    assert stats.closed_loop_rate(steady) == pytest.approx(8.0)
    # a 5 s stall between batches 5 and 6 moves the rate
    stalled = steady[:5] + [(a + 5, b + 5, c) for a, b, c in steady[5:]]
    assert stats.closed_loop_rate(stalled) == pytest.approx(80 / 15)
    # unconverged systems do not count
    assert stats.closed_loop_rate([(0.0, 2.0, 6)]) == pytest.approx(3.0)


def test_a_stall_moves_p90_of_an_open_loop():
    gaps = traffic.poisson_gaps(2.0, 50.0)
    due = np.cumsum(gaps)
    service = 0.3
    done = due + service
    base = stats.percentile(done - due, 90)
    # the server stalls 10 s from t = 20: everything due then waits it out
    stall = (due >= 20.0) & (due < 30.0)
    done_stalled = np.where(stall, 30.0 + service, done)
    p90 = stats.percentile(done_stalled - due, 90)
    assert base == pytest.approx(service)
    assert p90 > 2.0
    assert stats.percentile(done_stalled - due, 50) == pytest.approx(service)


def test_every_seed_gets_the_same_arrivals_in_another_order():
    mix = {"kind": "open_poisson", "rate_per_s": 3.0,
           "server": {"max_batch": 8}}
    a = traffic.make(mix, 1, 45.0)
    b = traffic.make(mix, 2**31 + 5, 45.0)
    assert a.arrivals.size == b.arrivals.size == 135

    def gaps_of(mix):
        return np.diff(mix.arrivals, prepend=0.0)

    np.testing.assert_allclose(np.sort(gaps_of(a)), np.sort(gaps_of(b)),
                               rtol=0, atol=1e-12)
    assert not np.array_equal(a.arrivals, b.arrivals)
    assert a.arrivals[-1] == pytest.approx(45.0)
    gaps = traffic.poisson_gaps(3.0, 45.0)
    assert gaps.sum() == pytest.approx(45.0)
    assert gaps.mean() == pytest.approx(1 / 3.0)
