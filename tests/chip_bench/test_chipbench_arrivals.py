"""The benchmark's own copies of the arrival processes, and its schedule."""
import numpy as np
import pytest

from chipbench import arrivals


@pytest.mark.parametrize("rate", [2.0, 12.5])
def test_poisson_reproduces_its_rate(rate):
    horizon = 2000.0
    ts = arrivals.poisson_arrivals(np.random.default_rng(7), rate, horizon)
    n = len(ts)
    assert abs(n - rate * horizon) < 4 * np.sqrt(rate * horizon)
    assert np.all(np.diff(ts) > 0) and ts[0] >= 0 and ts[-1] < horizon
    again = arrivals.poisson_arrivals(np.random.default_rng(7), rate,
                                      horizon)
    np.testing.assert_array_equal(ts, again)


def _count_cv2(ts, horizon, width):
    counts = np.histogram(ts, bins=np.arange(0.0, horizon + width, width))[0]
    return counts.var() / counts.mean()


def test_mmpp_keeps_the_rate_and_clumps():
    rate, horizon = 10.0, 4000.0
    rng = np.random.default_rng(11)
    bursty = arrivals.bursty_arrivals(rng, rate, horizon, burst=4.0,
                                      dwell=1.0)
    plain = arrivals.poisson_arrivals(np.random.default_rng(11), rate,
                                      horizon)
    assert abs(len(bursty) / horizon - rate) < 0.05 * rate
    # index of dispersion of 1-second counts: 1 for Poisson, above for MMPP
    assert _count_cv2(plain, horizon, 1.0) < 1.2
    assert _count_cv2(bursty, horizon, 1.0) > 2.0


@pytest.mark.parametrize("bad", [{"burst": 0.5}, {"dwell": 0.0}])
def test_mmpp_rejects_bad_parameters(bad):
    with pytest.raises(ValueError):
        arrivals.bursty_arrivals(np.random.default_rng(0), 1.0, 10.0, **bad)


@pytest.mark.parametrize("process", ["poisson", "bursty"])
def test_schedule_is_one_pattern_rotated_by_the_seed(process):
    traffic = {"process": process, "rate": 9.0}
    a = arrivals.schedule(traffic, 2**31 + 5, 40.0)
    b = arrivals.schedule(traffic, 17, 40.0)
    assert len(a) == len(b) > 0
    assert np.all((a >= 0) & (a < 40.0)) and np.all(np.diff(a) >= 0)
    np.testing.assert_array_equal(a, arrivals.schedule(traffic, 2**31 + 5,
                                                       40.0))
    assert not np.array_equal(a, b)
    # the same cyclic gaps: sorted circular gaps agree
    def gaps(t):
        return np.sort(np.diff(np.concatenate([t, [t[0] + 40.0]])))
    np.testing.assert_allclose(gaps(a), gaps(b), atol=1e-9)
