"""The spread that bounds are set from (``bench/sets.py``)."""
import statistics

import pytest

import sets


def test_spread_is_the_quartile_distance_over_the_median():
    v = [6772.0, 6854.0, 7120.0, 7156.0, 7230.0, 7647.0]
    q = statistics.quantiles(v, n=4)
    assert sets.spread(v) == pytest.approx((q[2] - q[0]) / 7138.0)
    # Python's default (exclusive) quartiles, not numpy's closer ones
    assert sets.spread(v) == pytest.approx((7334.25 - 6833.5) / 7138.0)


def test_summary_per_set_and_metric():
    def row(s, x, correct=True):
        return {"set": s, "line": {"correct": correct, "metrics": {
            "train_tokens_per_s": {"value": x, "unit": "tokens/s"}}}}
    rows = [row(1, 10.0), row(1, 11.0), row(1, 12.0), row(2, 9.0),
            {"set": 2, "line": None}]
    got = sets.summary(rows)
    assert set(got) == {(1, "train_tokens_per_s")}
    med, sp, values = got[(1, "train_tokens_per_s")]
    assert med == 11.0 and values == [10.0, 11.0, 12.0]
    assert sp == pytest.approx(2.0 / 11.0)
