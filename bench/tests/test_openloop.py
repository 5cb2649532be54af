"""The open-loop schedule and the exact percentile."""
from __future__ import annotations

import numpy as np
import pytest

from harness import openloop, stats

MIX = dict(sizes=[1, 4, 16, 64], shares=[0.4, 0.3, 0.2, 0.1],
           kinds=["transform", "predict", "quantization_errors"])


def _sched(seed, rate=400.0, seconds=5.0):
    return openloop.schedule(np.random.default_rng(seed), rate, seconds,
                             **MIX)


def test_same_seed_same_schedule():
    a, b = _sched(7), _sched(7)
    assert np.array_equal(a["t"], b["t"]) and a["kind"] == b["kind"]


def test_every_seed_gets_the_same_work_in_another_order():
    a, b = _sched(7), _sched(3_000_000_123)
    assert len(a["t"]) == len(b["t"]) == 2000
    assert sorted(a["size"]) == sorted(b["size"])
    assert sorted(a["kind"]) == sorted(b["kind"])
    # the same gaps: the spans differ only by which gap came first
    assert abs(a["t"][-1] - b["t"][-1]) <= np.log(2 * 2000) / 400.0
    assert not np.array_equal(a["size"], b["size"])
    counts = {s: int(np.sum(a["size"] == s)) for s in MIX["sizes"]}
    assert counts == {1: 800, 4: 600, 16: 400, 64: 200}


def test_arrivals_average_the_rate():
    s = _sched(11, rate=400.0, seconds=5.0)
    assert s["t"][0] == 0.0 and np.all(np.diff(s["t"]) >= 0)
    assert 4.5 < s["t"][-1] < 5.0


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile(list(range(1, 21)), 95) == 19
    with pytest.raises(ValueError):
        stats.percentile([], 50)
