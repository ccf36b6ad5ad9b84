import math

import numpy as np
import pytest

from bench.core import stats
from bench.core.driver import Outcome


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    x = rng.exponential(size=37).tolist()
    for q in (0, 5, 50, 95, 100):
        assert stats.percentile(x, q) == pytest.approx(np.percentile(x, q))


def test_percentile_with_missing():
    assert stats.percentile([1, 2, math.inf], 50) == 2
    assert stats.percentile([1, math.inf], 100) == math.inf


def _o(rid, due, end, reason=None):
    return Outcome(rid, "win", due, 10, reason == "gate-reject",
                   end=end, reason=reason)


def test_failures_count_as_missing_and_rejects_do_not_count():
    outs = [_o(0, 0.0, 1.0), _o(1, 0.0, 2.0),
            _o(2, 0.0, 0.1, "gate-reject"),
            _o(3, 1.0, 1.5, "deadline"), _o(4, 2.0, None)]
    lat = stats.latency_sample(outs, censor_at=100.0)
    assert sorted(lat)[:2] == [1.0, 2.0]
    assert len(lat) == 4  # the gate-reject is not a sample
    assert min(lat[2:]) > 90  # the failed and the unfinished are missing
    assert stats.failed_count(outs) == 2
