import statistics

import numpy as np
import pytest

from kgbench import arith


@pytest.mark.parametrize("n", [1, 2, 7, 100, 1001])
@pytest.mark.parametrize("q", [0, 50, 95, 99, 100])
def test_percentile_is_numpys_linear(n, q):
    vals = np.random.default_rng(n).exponential(size=n)
    assert arith.percentile(vals, q) == pytest.approx(np.percentile(vals, q))


def test_percentile_refuses_empty_and_bad_q():
    with pytest.raises(ValueError):
        arith.percentile([], 50)
    with pytest.raises(ValueError):
        arith.percentile([1.0], 101)


def test_rate_is_all_work_over_all_time():
    assert arith.rate(3000, 1.5) == 2000.0
    with pytest.raises(ValueError):
        arith.rate(1, 0.0)


def test_tail_is_over_every_batch():
    lat = [0.1] * 190 + [1.0] * 10
    # 10 slow batches out of 200: the 95th percentile sits at their edge
    assert arith.percentile(lat, 95) == pytest.approx(0.1 + 0.05 * 0.9)
    assert arith.percentile(lat, 96) == pytest.approx(1.0)


def test_spread_is_iqr_over_median():
    vals = [10.0, 11.0, 9.0, 10.5, 9.5, 10.0]
    q1, med, q3 = statistics.quantiles(vals, n=4)
    assert arith.spread(vals) == pytest.approx((q3 - q1) / med)
