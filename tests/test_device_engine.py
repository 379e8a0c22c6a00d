"""The device-resident engine: upload-once DeviceIndex, lean planning,
and the fused fold — counts AND docs bit-identical to the per-query loop
at every depth and arity (the loop ≡ batched ≡ device property chain).
"""

import jax
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # hypothesis, or fallback

from repro.core.batched_query import batched_query, plan_segment_pairs
from repro.core.cluster_index import build_cluster_index
from repro.core.device_engine import (
    device_counts,
    device_index,
    lower_plan,
    shard_mesh,
    sharded_device_counts,
    sharded_device_index,
)
from repro.core.queries import ConjunctiveQueries
from repro.core.reorder import cluster_ranges, reorder_permutation
from repro.data.corpus import Corpus
from repro.index.build import build_index, permute_docs
from repro.kernels.intersect.ref import PAD


def _random_setup(rng, n_docs, n_terms, k, mean_len=12):
    doc_lens = rng.integers(1, 2 * mean_len, n_docs)
    rows, ptr = [], [0]
    for d in range(n_docs):
        r = np.unique(rng.integers(0, n_terms, doc_lens[d]))
        rows.append(r)
        ptr.append(ptr[-1] + len(r))
    corpus = Corpus(
        doc_ptr=np.asarray(ptr, np.int64),
        doc_terms=np.concatenate(rows).astype(np.int32),
        n_terms=n_terms,
    )
    assign = rng.integers(0, k, n_docs)
    assign[rng.integers(0, n_docs)] = k - 1
    perm = reorder_permutation(assign, k)
    ranges = cluster_ranges(assign, k)
    index = build_index(corpus)
    reordered = permute_docs(index, perm)
    return index, build_cluster_index(reordered, ranges)


def _random_ragged_queries(rng, n_q, n_terms, max_arity=5):
    lists = []
    for _ in range(n_q):
        a = int(rng.integers(1, max_arity + 1))
        t = rng.integers(0, n_terms, a).tolist()
        if a >= 2 and rng.random() < 0.25:
            t[1] = t[0]  # duplicate term: ∩ is idempotent
        lists.append(t)
    return ConjunctiveQueries.from_lists(lists)


def _assert_device_matches_loop(cidx, cq):
    ptr, docs, _work = batched_query(cidx, cq)
    counts, docs_dev, info = device_counts(cidx, cq, return_docs=True)
    np.testing.assert_array_equal(counts, np.diff(ptr))
    np.testing.assert_array_equal(docs_dev, docs)
    for i, terms in enumerate(cq):
        r, _w = cidx.query(*terms)
        assert counts[i] == len(r)
    return info


@settings(max_examples=8, deadline=None)
@given(st.data())
def test_device_engine_equivalence_random_corpora(data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    index, cidx = _random_setup(
        rng,
        data.draw(st.integers(50, 250)),
        data.draw(st.integers(20, 200)),
        data.draw(st.integers(1, 10)),
    )
    cq = _random_ragged_queries(rng, data.draw(st.integers(1, 30)), index.n_terms)
    info = _assert_device_matches_loop(cidx, cq)
    assert info["n_kernel_calls"] == 1.0  # the whole chain, one dispatch


def test_device_engine_absent_terms_and_empty_postings(rng):
    index, cidx = _random_setup(rng, 150, 500, k=8)
    df = np.diff(index.post_ptr)
    empty = np.flatnonzero(df == 0)
    alive = np.flatnonzero(df > 0)
    cq = ConjunctiveQueries.from_lists(
        [
            [int(empty[0])],
            [int(empty[0]), int(empty[1]), int(empty[2])],
            [int(alive[0]), int(empty[0]), int(alive[1])],
            [int(alive[0]), int(alive[1]), int(alive[2])],
            [int(alive[3])],
        ]
    )
    counts, _info = device_counts(cidx, cq)
    assert counts[0] == 0 and counts[1] == 0 and counts[2] == 0
    _assert_device_matches_loop(cidx, cq)


def test_device_engine_every_depth(small_corpus):
    """L = 1 / 2 / 3 hierarchies return identical device counts."""
    from repro.core.seclud import SecludPipeline
    from repro.data.query_log import synth_query_log

    log = synth_query_log(small_corpus, n_queries=150, seed=7, arity=(2, 3))
    pipe = SecludPipeline(tc=800, doc_grained_below=256, seed=0)
    cq = log.as_conjunctive()[:60]
    ref = None
    for levels in (1, 2, 3):
        res = pipe.fit(small_corpus, k=8, algo="topdown", log=log, levels=levels)
        hidx = res.hier_index
        # fit() already uploaded: device_counts must reuse that copy.
        assert res.device_index is device_index(hidx)
        info = _assert_device_matches_loop(hidx, cq)
        counts, _ = device_counts(hidx, cq)
        if ref is None:
            ref = counts
        else:
            np.testing.assert_array_equal(counts, ref)
        assert info["padding_overhead"] <= 1.5  # tiny corpora pad a bit more


def test_device_index_is_cached_and_shared(rng):
    index, cidx = _random_setup(rng, 120, 60, k=5)
    di = device_index(cidx)
    assert device_index(cidx) is di  # cached on the hier view
    assert cidx.device() is di and cidx.as_hier().device() is di
    assert di.n_postings == len(cidx.index.post_docs)
    assert di.nbytes > 0
    # resident levels mirror the host CSR exactly
    np.testing.assert_array_equal(
        np.asarray(di.levels[0].cl_ids), cidx.cl_ids
    )
    # the resident postings are the host's, PAD-padded to whole tiles
    resident = np.asarray(di.post_docs)
    n = len(cidx.index.post_docs)
    assert len(resident) % 1024 == 0
    np.testing.assert_array_equal(resident[:n], cidx.index.post_docs)
    assert (resident[n:] == PAD).all()


def test_fit_shares_upload_with_cluster_index(small_corpus):
    from repro.core.seclud import SecludPipeline
    from repro.data.query_log import synth_query_log

    log = synth_query_log(small_corpus, n_queries=100, seed=3)
    pipe = SecludPipeline(tc=800, doc_grained_below=256, seed=0)
    res = pipe.fit(small_corpus, k=6, algo="topdown", log=log)
    # At L = 2 the facade's hier view IS the fitted hier index, so the
    # benchmark path batched_counts(res.cluster_index, ...) reuses the
    # fit-time upload instead of re-uploading.
    assert res.cluster_index.as_hier() is res.hier_index
    assert device_index(res.cluster_index) is res.device_index


def test_search_service_device_paths(rng):
    from repro.serve.search_service import SearchService

    index, cidx = _random_setup(rng, 300, 120, k=7)

    class _Res:
        cluster_index = cidx

    svc = SearchService(_Res())
    cq = _random_ragged_queries(rng, 40, 120)
    counts, _ = svc.serve_counts(cq)
    dev_counts, info = svc.serve_counts_device(cq)
    np.testing.assert_array_equal(dev_counts, counts)
    assert svc.device_index is device_index(cidx)  # persistent, shared
    # the packed/sharded path (now through ops.intersect_members) agrees
    packed = svc.pack(cq)
    np.testing.assert_array_equal(
        np.asarray(SearchService.device_counts(packed)), counts
    )


def test_lower_plan_layout(rng):
    index, cidx = _random_setup(rng, 200, 80, k=6)
    cq = _random_ragged_queries(rng, 25, 80)
    plan = plan_segment_pairs(cidx, cq)
    lowered = lower_plan(plan)
    # groups are permuted arity-descending; stage s touches the prefix
    # of groups with arity > s and nothing else
    sorted_arity = plan.arity[lowered.order]
    assert (np.diff(sorted_arity) <= 0).all()
    for i, n_g in enumerate(lowered.group_prefix):
        stage = i + 1  # chain stage number
        assert (sorted_arity[:n_g] > stage).all()
        if n_g < len(sorted_arity):
            assert (sorted_arity[n_g:] <= stage).all()
    # tail cells are dead: post PAD, group == G, query >= n_queries, arity 0
    n_true = lowered.n_cells_true
    assert (lowered.cells[0, n_true:] == PAD).all()
    assert (lowered.cells[1, n_true:] == len(lowered.order)).all()
    assert (lowered.cells[2, n_true:] >= lowered.n_queries).all()
    assert (lowered.cells[3, n_true:] == 0).all()
    assert lowered.n_cells % 8 == 0
    # live cells carry their group's arity (the stage mask's source)
    np.testing.assert_array_equal(
        lowered.cells[3, :n_true],
        np.repeat(plan.arity[lowered.order], lowered.cell_counts),
    )


def test_quantized_shapes_share_jit_signature():
    """Nearby batch sizes must land on the same quantized shapes (the
    fused fold's jit cache key), within a bounded <= 12.5% waste."""
    from repro.core.device_engine import _quantize

    assert _quantize(1000) == _quantize(1024) == 1024
    assert _quantize(37000) == _quantize(36001)
    for n in (1, 7, 9, 100, 5000, 123456):
        q = _quantize(n)
        assert q >= n and q <= max(8, int(n * 1.125)) + 8
        assert q % 8 == 0


def test_lean_planning_same_layout_zero_work(rng):
    index, cidx = _random_setup(rng, 180, 90, k=5)
    cq = _random_ragged_queries(rng, 30, 90)
    full = plan_segment_pairs(cidx, cq)
    lean = plan_segment_pairs(cidx, cq, track_work=False)
    for f in ("pair_query", "cluster", "base", "arity", "seg_ptr",
              "seg_start", "seg_len"):
        np.testing.assert_array_equal(
            getattr(full, f), getattr(lean, f), err_msg=f
        )
    assert full.cluster_work.sum() >= 0
    assert lean.cluster_work.sum() == 0  # work accounting skipped


def test_device_engine_empty_batch_and_empty_plan(rng):
    index, cidx = _random_setup(rng, 100, 400, k=4)
    counts, info = device_counts(cidx, np.empty((0, 2), np.int64))
    assert len(counts) == 0 and info["n_pairs"] == 0.0
    counts, docs, info = device_counts(
        cidx, np.empty((0, 2), np.int64), return_docs=True
    )
    assert len(docs) == 0
    # absent term => empty plan with a nonzero batch
    df = np.diff(index.post_ptr)
    empty_t = int(np.flatnonzero(df == 0)[0])
    counts, info = device_counts(cidx, np.array([[empty_t, empty_t]]))
    assert counts.tolist() == [0]


def test_device_counts_info_contract(rng):
    index, cidx = _random_setup(rng, 250, 100, k=6)
    cq = _random_ragged_queries(rng, 50, 100)
    counts, info = device_counts(cidx, cq)
    assert {"n_pairs", "n_kernel_calls", "padding_overhead", "occupancy",
            "stages"} <= set(info)
    assert info["n_kernel_calls"] == 1.0
    assert 0.0 < info["occupancy"] <= 1.0
    for s in info["stages"]:
        assert {"stage", "cur_cells", "cur_live", "long_cells",
                "padding_overhead"} <= set(s)
        assert s["padding_overhead"] >= 1.0 or s["long_cells"] == 0
        assert s["cur_live"] <= s["cur_cells"]


def test_device_docs_drop_pad_holes(rng):
    """Survivor docs come back in plan order with every PAD hole gone."""
    index, cidx = _random_setup(rng, 150, 60, k=4)
    cq = _random_ragged_queries(rng, 20, 60, max_arity=4)
    _ptr, docs, _w = batched_query(cidx, cq)
    _c, docs_dev, _i = device_counts(cidx, cq, return_docs=True)
    assert docs_dev.dtype == np.int32
    assert int(PAD) not in set(docs_dev.tolist())
    np.testing.assert_array_equal(docs_dev, docs)


def _search_exact_setup(L):
    """An index whose query ``[1, 0]`` probes term 0's segment of exactly
    ``L`` postings in cluster 1.  Term 0 also fills all of cluster 0, so
    that segment starts at an unaligned position, and it ends where term
    1's list begins, so its last row straddles a term boundary.  Term 1
    sits in ``min(L, 40)`` docs of cluster 1, half of them without term
    0; term 2 fills every doc that would otherwise be empty."""
    rng = np.random.default_rng(L)
    c0 = 293  # cluster 0: term 0 only
    hit = np.arange(L)  # cluster-1 docs (offsets) holding term 0
    m = min(L, 40)
    extra = max(m // 2, 1)
    n1 = L + extra
    with_t1 = set(rng.choice(L, m - m // 2, replace=False).tolist()) if L else set()
    with_t1 |= {L + e for e in range(m // 2)}
    rows = [[0] for _ in range(c0)]
    for d in range(n1):
        terms = ([0] if d < L else []) + ([1] if d in with_t1 else [])
        rows.append(terms + [2] if not terms or d % 5 == 0 else terms)
    ptr = np.concatenate([[0], np.cumsum([len(r) for r in rows])])
    corpus = Corpus(doc_ptr=ptr.astype(np.int64),
                    doc_terms=np.concatenate(rows).astype(np.int32), n_terms=3)
    assign = np.concatenate([np.zeros(c0, int), np.ones(n1, int)])
    reordered = permute_docs(build_index(corpus), reorder_permutation(assign, 2))
    return build_cluster_index(reordered, cluster_ranges(assign, 2))


@pytest.mark.parametrize("L", [0, 1, 127, 128, 129, 16383, 16384, 16385, 140_000])
def test_segment_search_exact(L, monkeypatch):
    """The 128-ary segment search answers exactly what ``np.isin`` over
    the segment does, for a probed segment of ``L`` postings: at an
    unaligned and an aligned start, next to other terms' postings in the
    same rows, for PAD cells and past the live count; in small blocks, so
    the last block overlaps the one before.  Then the whole fold, single
    device and sharded, against the host ``batched_query``."""
    from repro.core import device_engine as de

    rng = np.random.default_rng(L + 1)
    monkeypatch.setattr(de, "_SEARCH_BLOCK", 64)
    # Direct: three lists back to back; the segment is a slice of the middle one.
    before = np.sort(rng.choice(10**9, 93, replace=False)) + 10**9
    term = np.sort(rng.choice(10**9, L + 37 + 50 + 128, replace=False))
    after = np.sort(rng.choice(10**6, 70, replace=False))
    post = np.concatenate([before, term, after]).astype(np.int32)
    padded = de._pad_tiles(post)
    fences = de._fences(padded, de._search_levels(len(term)))
    lo1 = len(before) + 37  # unaligned
    lo2 = -(-lo1 // 128) * 128  # aligned
    cur, lo = [], []
    for start in (lo1, lo2):
        seg = post[start : start + L]
        probe = np.concatenate([
            seg if L <= 300 else rng.choice(seg, 300, replace=False),
            post[[start - 1, min(start + L, len(post) - 1)]],  # just outside
            before[:5], after[:5], rng.integers(0, 2**31 - 2, 20), [PAD],
        ])
        cur.append(probe)
        lo.append(np.full(len(probe), start))
    cur = np.concatenate(cur).astype(np.int32)
    lo = np.concatenate(lo).astype(np.int32)
    hi = lo + L
    n_live = len(cur) - 7  # the last cells are past the live count
    search = jax.jit(lambda *a: de._search_segments(*a))
    levels = de._search_levels(L)
    found = np.asarray(search(padded, fences[: levels - 1], cur, lo, hi, n_live))
    want = np.array([np.isin(c, post[a:b]) for c, a, b in zip(cur, lo, hi)])
    want[n_live:] = False
    np.testing.assert_array_equal(found, want)
    assert want.any() == (L > 0)

    # The fold: [1, 0] probes the segment; [1] is inactive at stage 1;
    # [1, 0, 2] adds a stage; quantization adds PAD cells.
    cidx = _search_exact_setup(L)
    cq = ConjunctiveQueries.from_lists([[1, 0], [1], [1, 0, 2], [0, 2], [2, 1]])
    plan = plan_segment_pairs(cidx.as_hier(), cq, track_work=False)
    if L > 1:
        low = lower_plan(plan)
        assert low.stage_seg[1, : low.group_width].max() == L
        assert low.stage_levels[0] == levels
        assert low.n_cells > low.n_cells_true  # PAD cells ride along
    ptr, docs, _w = batched_query(cidx, cq)
    mesh = shard_mesh(2)
    for run in (
        lambda: device_counts(cidx, cq, return_docs=True),
        lambda: sharded_device_counts(
            cidx, cq, sidx=sharded_device_index(cidx, mesh=mesh), return_docs=True),
    ):
        counts, docs_dev, info = run()
        np.testing.assert_array_equal(counts, np.diff(ptr))
        np.testing.assert_array_equal(docs_dev, docs)
        assert info["search_reads"] == sum(s["reads"] for s in info["stages"])
