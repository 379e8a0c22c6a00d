import numpy as np

from bench import data
from bench.reference import TermLists, reference_counts


def _corpus(n_docs, seed=3):
    return data.synth_corpus(data.CorpusSpec(
        n_docs=n_docs, n_terms=4000, mean_doc_len=21.5, sigma_doc_len=0.5, zipf_s=1.07,
        n_topics=48, topicality=0.6, topic_boost=40.0, topic_block_lo=64, topic_block_hi=None,
        seed=seed))


def test_reference_matches_brute_force():
    c = _corpus(3000)
    sets = [set(c.doc_terms[c.doc_ptr[d]:c.doc_ptr[d + 1]].tolist()) for d in range(c.n_docs)]
    log = data.synth_query_log(c, 200, 0.85, 0.5, 0.5, [9, 0], (2, 3, 5), (0.5, 0.3, 0.2))
    qs = log.term_lists()
    want = [sum(all(t in s for t in q) for s in sets) for q in qs]
    assert reference_counts(c, qs).tolist() == want
    assert sum(want) > 0


def test_control_differs_where_ids_exceed_16_bits():
    # The control cuts 32-bit ids to 16: past 65,536 documents it gives
    # wrong answers on every seed, and none below.
    for seed in (1, 2, 3):
        c = _corpus(70_000, seed=seed)
        log = data.synth_query_log(c, 128, 0.0, 0.0, 1.0, [seed, 0], (2,), (1.0,))
        qs = log.term_lists()
        ref = TermLists(c, [t for q in qs for t in q])
        want = np.array([ref.count(q) for q in qs])
        assert (np.array([ref.count(q, 16) for q in qs]) != want).sum() > 0
    small = _corpus(3000)
    qs = data.synth_query_log(small, 64, 0.0, 0.0, 1.0, [5, 0], (2,), (1.0,)).term_lists()
    ref = TermLists(small, [t for q in qs for t in q])
    assert [ref.count(q, 16) for q in qs] == [ref.count(q) for q in qs]


def test_stratified_mix_is_exact():
    c = _corpus(2000)
    log = data.synth_query_log(c, 1000, 0.85, 0.5, 0.5, [4, 0], (2, 3, 5), (0.5, 0.3, 0.2))
    ar = log.arities()
    assert [(ar == a).sum() for a in (2, 3, 5)] == [500, 300, 200]
    for row in log.term_lists():
        assert len(set(row)) == len(row)
    assert np.array_equal(log.queries,
                          data.synth_query_log(c, 1000, 0.85, 0.5, 0.5, [4, 0], (2, 3, 5),
                                               (0.5, 0.3, 0.2)).queries)
