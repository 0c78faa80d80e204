"""The metric arithmetic: exact percentiles, rates over the whole window."""
import numpy as np
import pytest

from bench import stats


@pytest.mark.parametrize("p", [0, 50, 95, 99, 100])
def test_percentile_is_exact(p):
    v = np.random.default_rng(1).exponential(size=1001)
    assert stats.percentile(v.tolist(), p) == pytest.approx(
        np.percentile(v, p), rel=1e-12)


def test_percentile_small_samples():
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1.0, 2.0], 50) == 1.5
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_counts_the_whole_window():
    assert stats.rate(300, 10.0, 12.5) == pytest.approx(120.0)
    with pytest.raises(ValueError):
        stats.rate(1, 2.0, 2.0)
