import statistics

import numpy as np
import pytest

from lib import stats


@pytest.mark.parametrize("q", [50, 90, 95, 99])
def test_percentile_is_numpys_linear_rule(q):
    values = np.random.default_rng(q).lognormal(size=257).tolist()
    assert stats.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_percentile_of_nothing_and_of_one():
    assert stats.percentile([], 95) is None
    assert stats.percentile([3.0], 95) == 3.0


def test_ten_samples_beyond():
    # 220 requests: 11 lie beyond the 95th percentile, 2 beyond the 99th
    assert stats.samples_beyond(220, 95) == 11
    assert stats.samples_beyond(220, 99) == 2
    assert stats.highest_supported_percentile(220) == 95
    assert stats.highest_supported_percentile(199) == 90
    assert stats.highest_supported_percentile(1000) == 99
    assert stats.highest_supported_percentile(12) == 50


def test_spread_is_the_contracts():
    values = [27181.0, 27200.0, 27150.0, 27190.0, 27230.0, 27175.0]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))
