"""The REPRO_DEBUG runtime head: validate() passes on every structure
the engine actually builds, rejects corrupted copies, and stays inert
(zero work) when debug mode is off."""

import dataclasses

import numpy as np
import pytest

from repro.analysis.runtime import debug_enabled, force_debug, maybe_validate
from repro.core.batched_query import plan_segment_pairs
from repro.core.cluster_index import build_cluster_index
from repro.core.device_engine import (
    device_index,
    shard_mesh,
    sharded_device_index,
)
from repro.core.queries import ConjunctiveQueries
from repro.core.reorder import cluster_ranges, reorder_permutation
from repro.data.corpus import Corpus
from repro.index.build import build_index, permute_docs


@pytest.fixture(scope="module")
def cidx():
    rng = np.random.default_rng(11)
    n_docs, n_terms, k = 260, 110, 7
    rows, ptr = [], [0]
    for _ in range(n_docs):
        r = np.unique(rng.integers(0, n_terms, 16))
        rows.append(r)
        ptr.append(ptr[-1] + len(r))
    corpus = Corpus(
        doc_ptr=np.asarray(ptr, np.int64),
        doc_terms=np.concatenate(rows).astype(np.int32),
        n_terms=n_terms,
    )
    assign = rng.integers(0, k, n_docs)
    perm = reorder_permutation(assign, k)
    ranges = cluster_ranges(assign, k)
    reordered = permute_docs(build_index(corpus), perm)
    return build_cluster_index(reordered, ranges)


@pytest.fixture(scope="module")
def plan(cidx):
    rng = np.random.default_rng(12)
    lists = [
        rng.integers(0, 110, int(rng.integers(1, 5))).tolist() for _ in range(30)
    ]
    return plan_segment_pairs(cidx, ConjunctiveQueries.from_lists(lists))


def test_debug_switch(monkeypatch):
    monkeypatch.delenv("REPRO_DEBUG", raising=False)
    assert not debug_enabled()
    monkeypatch.setenv("REPRO_DEBUG", "1")
    assert debug_enabled()
    monkeypatch.setenv("REPRO_DEBUG", "0")
    assert not debug_enabled()
    with force_debug(True):
        assert debug_enabled()  # override beats the env
        with force_debug(False):
            assert not debug_enabled()
        assert debug_enabled()


def test_maybe_validate_is_inert_when_off():
    class Bomb:
        def validate(self):  # must never run with debug off
            raise AssertionError("validate ran with REPRO_DEBUG off")

    with force_debug(False):
        b = Bomb()
        assert maybe_validate(b) is b
    with force_debug(True), pytest.raises(AssertionError):
        maybe_validate(Bomb())


def test_real_structures_validate_clean(cidx, plan):
    hidx = cidx.as_hier()
    with force_debug(True):
        maybe_validate(hidx)
        maybe_validate(plan)
        maybe_validate(device_index(cidx))
        maybe_validate(sharded_device_index(cidx, mesh=shard_mesh(4)))


def test_hier_index_rejects_corruption(cidx):
    hidx = cidx.as_hier()
    bad_ptr = hidx.index.post_ptr.copy()
    bad_ptr[1] = bad_ptr[-1] + 5  # not a CSR any more
    bad = dataclasses.replace(hidx, index=dataclasses.replace(hidx.index, post_ptr=bad_ptr))
    with pytest.raises(ValueError, match="post_ptr"):
        bad.validate()
    lev = hidx.levels[0]
    bad_ranges = lev.ranges.copy()
    if len(bad_ranges) > 2:
        bad_ranges[1], bad_ranges[2] = bad_ranges[2], bad_ranges[1] + 1
    bad = dataclasses.replace(hidx, levels=(dataclasses.replace(lev, ranges=bad_ranges),) + hidx.levels[1:])
    with pytest.raises(ValueError):
        bad.validate()


def test_segment_plan_rejects_corruption(plan):
    bad = dataclasses.replace(plan, arity=plan.arity + 1)  # breaks the CSR
    with pytest.raises(ValueError):
        bad.validate()
    bad_len = plan.seg_len.copy()
    if len(bad_len):
        bad_len[0] = -3
    bad = dataclasses.replace(plan, seg_len=bad_len)
    with pytest.raises(ValueError):
        bad.validate()


def test_device_index_rejects_corruption(cidx):
    di = device_index(cidx)
    bad = dataclasses.replace(di, n_docs=1)  # postings now out of range
    with pytest.raises(ValueError, match="doc ids"):
        bad.validate()
    bad = dataclasses.replace(di, search_levels=0)
    with pytest.raises(ValueError):
        bad.validate()


@pytest.fixture(scope="module")
def long_cidx():
    """An index with a 300-posting list, so the resident postings carry
    one fence."""
    rng = np.random.default_rng(13)
    n_docs, k = 300, 3
    rows = [np.unique(np.concatenate([[0], rng.integers(1, 40, 6)])) for _ in range(n_docs)]
    corpus = Corpus(
        doc_ptr=np.concatenate([[0], np.cumsum([len(r) for r in rows])]).astype(np.int64),
        doc_terms=np.concatenate(rows).astype(np.int32),
        n_terms=40,
    )
    assign = rng.integers(0, k, n_docs)
    reordered = permute_docs(build_index(corpus), reorder_permutation(assign, k))
    return build_cluster_index(reordered, cluster_ranges(assign, k))


@pytest.mark.parametrize("sharded", [False, True], ids=["single", "sharded"])
@pytest.mark.parametrize("entry", [1, -1], ids=["aligned_posting", "pad_tail"])
def test_corrupt_fence_fails_validate(long_cidx, sharded, entry):
    """A fence entry that disagrees with the posting at its aligned
    position (or a PAD tail entry overwritten) fails ``validate()``: the
    segment search would descend into the wrong block."""
    import jax

    if sharded:
        idx = sharded_device_index(long_cidx, mesh=shard_mesh(2))
        fence = np.asarray(idx.fences[0]).copy()
        fence[0, entry] ^= 1
        shard = idx.fences[0].sharding
    else:
        idx = device_index(long_cidx)
        fence = np.asarray(idx.fences[0]).copy()
        fence[entry] ^= 1
        shard = idx.fences[0].sharding
    assert idx.search_levels == 2
    idx.validate()  # the index as built passes
    bad = dataclasses.replace(idx, fences=(jax.device_put(fence, shard),))
    with pytest.raises(ValueError, match="fence"):
        bad.validate()


def test_sharded_index_rejects_corruption(cidx):
    sidx = sharded_device_index(cidx, mesh=shard_mesh(4))
    bad_counts = sidx.shard_counts.copy()
    bad_counts[0] += 1  # partition no longer exact
    bad = dataclasses.replace(sidx, shard_counts=bad_counts)
    with pytest.raises(ValueError):
        bad.validate()
    bad_bounds = sidx.doc_bounds.copy()
    bad_bounds[1] = bad_bounds[-1] + 1
    bad = dataclasses.replace(sidx, doc_bounds=bad_bounds)
    with pytest.raises(ValueError):
        bad.validate()


def test_build_paths_validate_under_debug(cidx):
    """The builders call maybe_validate on their own results — with the
    flag forced on, a full build + upload round-trip must stay clean."""
    with force_debug(True):
        hidx = cidx.as_hier()
        rng = np.random.default_rng(1)
        lists = [rng.integers(0, 110, 3).tolist() for _ in range(10)]
        cq = ConjunctiveQueries.from_lists(lists)
        plan_segment_pairs(hidx, cq)  # validated on return
        device_index(cidx)
        sharded_device_index(cidx, mesh=shard_mesh(2))
