import pytest

from harness import arith


def test_rate_is_count_over_all_the_time():
    assert arith.rate(512, 34.5) == pytest.approx(14.840579710144928)
    with pytest.raises(ValueError):
        arith.rate(3, 0.0)


def test_spread_is_the_drivers():
    v = [14.8, 14.9, 14.7, 15.0, 14.85, 14.82]
    import statistics
    q1, _, q3 = statistics.quantiles(v, n=4)
    assert arith.spread(v) == (q3 - q1) / statistics.median(v)


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert arith.union_seconds(iv) == pytest.approx(3.0)
    assert arith.gaps(iv, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert arith.gaps(iv, 0.25, 3.5) == [(2.0, 3.0)]
