import pytest

from cellbench import stats


def test_percentile_interpolates_between_ranks():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile(xs, 100) == 50.0
    assert stats.percentile([], 95) is None


def test_pooled_gaps_keep_the_gaps_that_end_in_the_window():
    # request A: tokens at 0, 1, 2.5; request B: at 1.9, 2.0, 4.0
    rows = [[0.0, 1.0, 2.5], [1.9, 2.0, 4.0]]
    gaps = sorted(stats.pooled_gaps(rows, 1.0, 3.0))
    # A's 1.0 (ends at 1.0) and 1.5 (ends 2.5); B's 0.1 (ends 2.0);
    # B's 2.0 ends at 4.0, outside
    assert gaps == pytest.approx([0.1, 1.0, 1.5])
    assert stats.count_in(rows, 1.0, 3.0) == 4

