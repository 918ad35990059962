import numpy as np
import pytest

from lib import stats


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 150])
def test_percentile_matches_numpy(q, n):
    v = np.random.default_rng(n).lognormal(0, 2, n)
    assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q),
                                                    rel=1e-12, abs=0)


def test_p90_of_a_window_keeps_ten_beyond_it():
    lat = np.arange(1, 101, dtype=float)        # 100 queries
    p90 = stats.percentile(lat, 90)
    assert p90 == pytest.approx(90.1)
    assert (lat > p90).sum() == 10


def test_percentile_of_nothing_is_nan():
    assert np.isnan(stats.percentile([], 90))


def test_rate_is_work_over_the_whole_window():
    assert stats.rate(24_576, 51.2) == pytest.approx(480.0)
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_queue_summary_matches_the_program():
    from repro.core import metrics

    rng = np.random.default_rng(3)
    n = 400
    submit = np.sort(rng.integers(0, 10_000, n))
    start = submit + rng.integers(0, 500, n)
    runtime = rng.integers(1, 3_000, n)
    res = {"submit": submit, "start": start, "finish": start + runtime,
           "runtime": runtime, "nodes": rng.integers(1, 64, n),
           "done": np.ones(n, bool), "valid": np.ones(n, bool)}
    want = metrics.summary(res, 128)
    want["p99_wait"] = metrics.percentiles(start - submit, 99)
    got = stats.queue_summary(res, 128)
    assert got.keys() == want.keys()
    for k in got:
        assert got[k] == want[k], k
