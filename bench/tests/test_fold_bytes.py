import numpy as np

from bench.fold_bytes import needed_bytes


def test_each_answer_in_each_list_and_one_count():
    # Three queries: 5 answers of a 2-term query, none of a 3-term one,
    # 1 of a 5-term one.
    got = needed_bytes(np.array([2, 3, 5]), np.array([5, 0, 1]))
    assert got == 4 * (2 * 5 + 3 * 0 + 5 * 1) + 4 * 3


def test_no_queries_need_nothing():
    assert needed_bytes(np.array([], np.int64), np.array([], np.int64)) == 0.0
