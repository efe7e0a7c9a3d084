"""Arrival processes: a seed fixes the schedule, another seed moves it,
and each process keeps its shape."""
import numpy as np
import pytest

from bench import manifest

ROUNDS = {"period_s": 5.0, "spread_s": 1.0, "per_round": 40}
POISSON = {"rate_qps": 3.0}


@pytest.mark.parametrize("name,params", [("rounds", ROUNDS),
                                         ("poisson", POISSON)])
def test_same_seed_same_schedule_other_seed_other(name, params):
    a = manifest.load_module("arrivals", name)
    s1 = a.schedule(params, 30.0, 2**33 + 5)
    assert np.array_equal(s1, a.schedule(params, 30.0, 2**33 + 5))
    s2 = a.schedule(params, 30.0, 2**33 + 6)
    assert len(s1) != len(s2) or not np.allclose(s1, s2)
    assert np.all(np.diff(s1) >= 0) and s1[0] >= 0 and s1[-1] < 30.0


def test_rounds_fall_in_their_spread():
    a = manifest.load_module("arrivals", "rounds")
    s = a.schedule(ROUNDS, 30.0, 1)
    assert len(s) == 6 * 40
    phase = np.mod(s, ROUNDS["period_s"])
    assert np.all(phase < ROUNDS["spread_s"])
    assert a.rate_qps(ROUNDS) == 8.0


def test_poisson_rate_and_gaps():
    a = manifest.load_module("arrivals", "poisson")
    s = a.schedule({"rate_qps": 50.0}, 200.0, 3)
    assert len(s) == pytest.approx(50 * 200, rel=0.05)
    gaps = np.diff(s)
    # exponential gaps: the standard deviation equals the mean
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.1)
