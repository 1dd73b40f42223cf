"""The traffic generator: every seed gets the same work in another order,
the ramp and the window each their own."""

import numpy as np

from chipbench.harness import traffic as T

TRAFFIC = {
    "arrivals": {"law": "exponential_gaps", "rate_per_s": 10.0},
    "prompt_tokens": {"law": "lognormal", "median": 96, "sigma": 0.9,
                      "min": 8, "max": 512},
    "output_tokens": {"law": "lognormal", "median": 96, "sigma": 0.7,
                      "min": 16, "max": 256},
    "ramp_s": 5.0,
}


def _parts(reqs):
    return ([r for r in reqs if not r[3]], [r for r in reqs if r[3]])


def test_the_window_holds_the_same_requests_under_every_seed():
    a = T.make_requests(TRAFFIC, 1000, 20.0, 1)
    b = T.make_requests(TRAFFIC, 1000, 20.0, 2 ** 31 + 5)
    for x, y, n, start, end in zip(_parts(a), _parts(b), (50, 200),
                                   (0.0, 5.0), (5.0, 25.0)):
        assert len(x) == len(y) == n
        assert sorted(len(r[1]) for r in x) == sorted(len(r[1]) for r in y)
        assert sorted(r[2] for r in x) == sorted(r[2] for r in y)
        assert [len(r[1]) for r in x] != [len(r[1]) for r in y]
        gaps = lambda rs: np.sort(np.diff([start] + [r[0] for r in rs]))  # noqa
        assert np.allclose(gaps(x), gaps(y), atol=1e-9)
        assert all(start < r[0] <= end + 1e-9 for r in x)
        assert abs(x[-1][0] - end) < 1e-9  # the last is due as the part ends
    assert [r[0] for r in a] == sorted(r[0] for r in a)


def test_same_seed_same_inputs_and_lengths_keep_their_limits():
    a = T.make_requests(TRAFFIC, 1000, 20.0, 7)
    b = T.make_requests(TRAFFIC, 1000, 20.0, 7)
    assert all((x[1] == y[1]).all() and x[0] == y[0] and x[2:] == y[2:]
               for x, y in zip(a, b))
    assert min(len(r[1]) for r in a) >= 8 and max(len(r[1]) for r in a) <= 512
    assert min(r[2] for r in a) >= 16 and max(r[2] for r in a) <= 256
    assert 80 <= np.median([len(r[1]) for r in a]) <= 112


def test_gaps_are_the_exponentials_and_come_in_bursts():
    t = T.arrival_times(TRAFFIC["arrivals"], 100.0, np.random.RandomState(3))
    gaps = np.diff(np.concatenate([[0.0], t]))
    assert len(t) == 1000 and abs(t[-1] - 100.0) < 1e-9
    # an exponential law: mean 1/rate, as much spread as mean, a median
    # of ln 2 / rate
    assert abs(gaps.mean() - 0.1) < 1e-9
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05
    assert abs(np.median(gaps) - np.log(2) / 10.0) < 2e-3
    # not evened out: the busiest second holds well over the mean of 10,
    # the quietest well under, as a drawn process would
    per_s = np.histogram(t, bins=100, range=(0.0, 100.0))[0]
    assert per_s.max() >= 16 and per_s.min() <= 5
    assert abs(per_s.var() / per_s.mean() - 1.0) < 0.35


def test_no_law_but_the_ones_named():
    import pytest

    rng = np.random.RandomState(0)
    with pytest.raises(ValueError):
        T.arrival_times({"law": "poisson", "rate_per_s": 1.0}, 2.0, rng)
    with pytest.raises(ValueError):
        T.lengths({"law": "uniform", "min": 1, "max": 2}, 3, rng)
