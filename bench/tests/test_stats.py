"""Percentile and censoring arithmetic of the end-to-end metrics."""

import math

import numpy as np
import pytest

from bench.harness import stats
from bench.harness.traffic import Req


@pytest.mark.parametrize("q", [0, 50, 90, 95, 100])
def test_percentile_matches_numpy_linear(q):
    v = list(np.random.default_rng(0).exponential(size=37))
    assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_percentile_empty_is_nan():
    assert math.isnan(stats.percentile([], 90))


def _req(due, times):
    return Req(rid=0, prompt=[1], max_new=10, due=due, token_t=list(times))


def test_ttft_from_due_time_and_censored_at_window_end():
    reqs = [_req(1.0, [1.5, 1.6]),        # 0.5 s
            _req(-2.0, [0.5]),            # due before the window: left out
            _req(8.0, []),                # no token: enters at 10 - 8
            _req(9.0, [10.5]),            # token after the close: 10 - 9
            _req(10.0, [10.1])]           # due at the close: left out
    assert stats.ttft(reqs, 10.0) == [0.5, 2.0, 1.0]


def test_gaps_count_pairs_inside_the_window():
    reqs = [_req(0.0, [-0.5, 0.2, 0.3, 0.7, 10.2]), _req(1.0, [2.0, 2.5])]
    assert stats.gaps(reqs, 10.0) == pytest.approx([0.1, 0.4, 0.5])
    assert stats.tokens_in(reqs, 10.0) == 5
