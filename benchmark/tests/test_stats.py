import pytest

from benchmark.harness import stats


def test_percentiles_against_hand_cases():
    xs = [10, 20, 30, 40, 50]
    assert stats.median(xs) == 30
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile(xs, 0) == 10 and stats.percentile(xs, 100) == 50
    assert stats.percentile([7], 95) == 7
    assert stats.percentile([], 95) is None


def test_summary_carries_its_sample_count():
    s = stats.summary(range(1, 101))
    assert s["n"] == 100 and s["max"] == 100
    assert s["p50"] == pytest.approx(50.5) and s["p95"] == pytest.approx(95.05)
    assert stats.summary([]) == {"n": 0}
