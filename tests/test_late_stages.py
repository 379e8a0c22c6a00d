"""Conjunctions of 3-5 terms through the serving loop, and the counters
of the fold's later stages.

Invariants under test: through ``AsyncServingLoop``, on the one-chip
path and on the sharded path over 4 devices, every count of a 3-5-term
conjunction equals a plain chained ``np.intersect1d`` over the corpus's
own term lists; ``info["late_cells"]`` is the cells that stages 2 on
carry into their searches and ``info["late_live"]`` those of them whose
first value lies in each of its group's earlier segments, equal to a
plain numpy count over the lowered plan and equal on both paths; a
two-term batch reports 0 for both.
"""

import asyncio

import jax
import numpy as np
import pytest

from repro.core.batched_query import plan_segment_pairs
from repro.core.device_engine import (
    device_counts,
    device_index,
    lower_plan,
    sharded_device_counts,
    sharded_device_index,
)
from repro.core.queries import as_queries
from repro.core.seclud import SecludPipeline
from repro.data.query_log import synth_query_log
from repro.serve.loop import AsyncServingLoop, ServeConfig
from repro.serve.search_service import SearchService

N_SHARDS = 4


@pytest.fixture(scope="module")
def fitted(small_corpus, small_log):
    pipe = SecludPipeline(tc=800, doc_grained_below=256, seed=0)
    return pipe.fit(small_corpus, k=12, algo="topdown", log=small_log)


@pytest.fixture(scope="module")
def long_queries(small_corpus):
    """Arities 3, 4 and 5 in the benchmark's mix, terms drawn by document
    frequency and independent."""
    log = synth_query_log(small_corpus, n_queries=96, seed=17, arity=(3, 4, 5),
                          arity_weights=(0.55, 0.30, 0.15), frequency_weight=1.0,
                          zipf_s=0.0, co_topic=0.0)
    return log.queries


@pytest.fixture(scope="module")
def term_docs(small_corpus):
    """Each term's sorted document ids, read off the corpus itself."""
    c = small_corpus
    doc_of = np.repeat(np.arange(c.n_docs), np.diff(c.doc_ptr))
    order = np.argsort(c.doc_terms, kind="stable")
    ptr = np.searchsorted(c.doc_terms[order], np.arange(c.n_terms + 1))
    docs = doc_of[order]
    return lambda t: docs[ptr[t] : ptr[t + 1]]


def _chained(term_docs, terms):
    want = term_docs(int(terms[0]))
    for t in terms[1:]:
        want = np.intersect1d(want, term_docs(int(t)))
    return len(want)


def _serve_through_loop(svc, queries):
    loop = AsyncServingLoop(svc, ServeConfig(max_batch=16, deadline_s=0.001))

    async def session():
        await loop.start()
        out = await asyncio.gather(*(loop.submit(q[q >= 0].tolist()) for q in queries))
        await loop.stop()
        return out

    return asyncio.run(session()), loop.stats


@pytest.mark.parametrize("sharded", [False, True])
def test_long_conjunctions_through_the_loop_are_exact(fitted, long_queries, term_docs,
                                                      sharded):
    svc = SearchService(fitted)
    if sharded:
        svc.enable_sharded(n_shards=N_SHARDS)
    counts, stats = _serve_through_loop(svc, long_queries)
    assert stats.n_batches >= 6
    want = [_chained(term_docs, q[q >= 0]) for q in long_queries]
    assert counts == want
    assert sum(want) > 0


def _late_live_by_numpy(low, post_docs):
    """Σ over stages s >= 2 of the true cells with arity > s whose first
    value lies in each of its group's rank-1 … rank-(s-1) segments."""
    n, w = low.n_cells_true, low.group_width
    post, group, arity = low.cells[0, :n], low.cells[1, :n], low.cells[3, :n]
    first = post_docs[post]
    live = np.ones(n, bool)
    total = 0
    for s in range(1, low.n_stages + 1):
        if s >= 2:
            total += int((live & (arity > s)).sum())
        start = low.stage_seg[0, (s - 1) * w + group]
        length = low.stage_seg[1, (s - 1) * w + group]
        inside = np.array([first[i] in post_docs[start[i] : start[i] + length[i]]
                           for i in range(n)], bool)
        live &= (arity <= s) | inside
    return total


@pytest.fixture(scope="module")
def both_paths(fitted, long_queries):
    """The one-chip and sharded ``info`` of each batch of 24, with the
    one-chip lowered plan."""
    hidx = SearchService(fitted).query_index
    dindex = device_index(hidx)
    sidx = sharded_device_index(hidx, n_shards=N_SHARDS)
    out = []
    for i in range(0, len(long_queries), 24):
        cq = as_queries(long_queries[i : i + 24])
        low = lower_plan(plan_segment_pairs(dindex.host, cq, track_work=False))
        c1, one = device_counts(hidx, cq, dindex=dindex)
        c4, four = sharded_device_counts(hidx, cq, sidx=sidx)
        assert c1.tolist() == c4.tolist()
        out.append((low, one, four))
    return dindex, out


def test_late_counters_count_the_later_stages(both_paths):
    dindex, batches = both_paths
    post_docs = np.asarray(jax.device_get(dindex.post_docs))
    for low, one, _four in batches:
        assert low.n_stages >= 3
        assert one["late_cells"] == sum(low.cell_prefix[1:]) > 0
        assert one["late_live"] == _late_live_by_numpy(low, post_docs)
        assert 0 < one["late_live"] < one["late_cells"]
        assert one["late_cells"] == sum(s["cur_cells"] for s in one["stages"][1:])
        assert one["late_live"] == sum(s["cur_live"] for s in one["stages"][1:])


def test_late_counters_agree_between_the_paths(both_paths):
    _dindex, batches = both_paths
    for _low, one, four in batches:
        assert four["late_cells"] == one["late_cells"]
        assert four["late_live"] == one["late_live"]
        assert [(s["cur_cells"], s["cur_live"]) for s in four["stages"]] == [
            (s["cur_cells"], s["cur_live"]) for s in one["stages"]]


@pytest.mark.parametrize("path", ["one_chip", "sharded"])
def test_two_term_batch_reports_no_late_cells(fitted, small_corpus, path):
    hidx = SearchService(fitted).query_index
    q = synth_query_log(small_corpus, n_queries=24, seed=3, arity=2).queries
    if path == "sharded":
        counts, info = sharded_device_counts(
            hidx, q, sidx=sharded_device_index(hidx, n_shards=N_SHARDS))
    else:
        counts, info = device_counts(hidx, q)
    assert info["cells_true"] > 0 and len(info["stages"]) == 1
    assert info["late_cells"] == 0.0 and info["late_live"] == 0.0
