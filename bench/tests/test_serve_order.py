"""The order a closed loop serves the fixed pool in: the same work for
every seed, each batch a spread of the pool's cost strata."""

import numpy as np
import pytest

from bench import data


def _order(seed, n=256, batch=16, passes=3):
    cost = np.random.default_rng(5).integers(1, 10**6, size=n)
    return cost, data.serve_order(cost, batch, passes, [seed, 3])


def test_every_pass_serves_the_whole_pool():
    cost, order = _order(2**40 + 17)
    for p in order.reshape(3, -1):
        assert np.array_equal(np.sort(p), np.arange(len(cost)))


def test_each_batch_holds_one_query_of_each_stratum():
    cost, order = _order(7)
    stratum = np.empty(len(cost), np.int64)
    stratum[np.argsort(cost, kind="stable")] = np.arange(len(cost)) // (len(cost) // 16)
    for b in order.reshape(-1, 16):
        assert np.array_equal(np.sort(stratum[b]), np.arange(16))


def test_the_seed_orders_and_only_orders():
    _, a = _order(11)
    _, b = _order(11)
    _, c = _order(12)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.array_equal(np.sort(a), np.sort(c))


def test_a_pool_of_part_batches_is_refused():
    with pytest.raises(ValueError):
        data.serve_order(np.arange(100), 16, 1, 0)
