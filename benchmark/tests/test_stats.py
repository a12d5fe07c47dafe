import math

import pytest

from benchmark.harness import fmt, stats


@pytest.mark.parametrize("xs, q, want", [
    ([1.0], 90, 1.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 90, 90.1),
    ([5, 1, 3], 0, 1.0),
    ([5, 1, 3], 100, 5.0),
])
def test_percentile(xs, q, want):
    assert stats.percentile(xs, q) == pytest.approx(want)


def test_percentile_matches_numpy():
    import numpy as np
    xs = list(np.random.default_rng(0).random(137))
    for q in (10, 50, 90, 95):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_geomean():
    assert stats.geomean([2.0, 8.0]) == pytest.approx(4.0)
    assert stats.geomean([0.65]) == pytest.approx(0.65)
    assert stats.geomean([2.2, 7.2]) == pytest.approx(math.sqrt(2.2 * 7.2))
    with pytest.raises(ValueError):
        stats.geomean([])
    with pytest.raises(ValueError):
        stats.geomean([1.0, 0.0])
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_decimal_text():
    assert fmt.dec(24469500, 2) == "244695.00"
    assert fmt.dec(5, 4) == "0.0005"
    assert fmt.dec(-1234, 2) == "-12.34"
    assert fmt.dec(7, 0) == "7"
    # avg of decimal(15,2): scale 6, half away from zero
    assert fmt.avg(100, 3, 2) == "0.333333"
    assert fmt.avg(200, 3, 2) == "0.666667"
    assert fmt.avg(5, 2, 4) == "0.00025000"
