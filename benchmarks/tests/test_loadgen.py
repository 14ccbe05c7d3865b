"""The generator's due-time arithmetic and its dealing of operations."""

import numpy as np

from harness.loadgen import deal_operations, due_times

MIX = {"rate_scale": 1.0, "operations": [
    {"name": "often", "target_throughput": 50},
    {"name": "seldom", "target_throughput": 1},
    {"name": "rare", "target_throughput": 0.02}]}


def test_due_times_fill_the_window():
    due = due_times(450, 15.0, np.random.default_rng(1))
    assert len(due) == 450
    assert due[0] == 0.0
    assert np.all(np.diff(due) > 0)
    # the first gap was moved to 0, so the last request is due inside
    # the window
    assert 14.9 < due[-1] < 15.0
    assert abs(np.mean(np.diff(due)) - 1 / 30.0) < 1e-3


def test_every_seed_gets_the_same_gaps_in_another_order():
    a = np.diff(due_times(200, 10.0, np.random.default_rng(1)))
    b = np.diff(due_times(200, 10.0, np.random.default_rng(2)))
    assert not np.allclose(a, b)
    # all but the one gap that each order puts first
    assert len(np.setdiff1d(np.round(a, 9), np.round(b, 9))) <= 1
    # exponential: the median gap is ln 2 of the mean
    assert abs(np.median(a) / np.mean(a) - np.log(2)) < 0.05


def test_operations_at_their_rates_and_never_none():
    dealt = deal_operations(MIX, 10.0, np.random.default_rng(3))
    assert sorted(dealt) == [0] * 500 + [1] * 10 + [2]
    assert dealt != sorted(dealt)
    assert dealt == deal_operations(MIX, 10.0, np.random.default_rng(3))
    other = deal_operations(MIX, 10.0, np.random.default_rng(4))
    assert other != dealt and sorted(other) == sorted(dealt)


def test_rate_scale_multiplies_every_rate():
    dealt = deal_operations(dict(MIX, rate_scale=0.5), 10.0,
                            np.random.default_rng(3))
    assert sorted(dealt) == [0] * 250 + [1] * 5 + [2]
