"""The benchmark's own data: the corpus, the query pool and its order.

Copied from the program's generators (``repro.data.corpus.synth_corpus``
and ``repro.data.query_log.synth_query_log``) so that no later change to
the program can move the data a cell measures.  The corpus generator is
the program's, draw for draw (its per-topic loop visits the tokens
through one sort instead of a mask per topic).  The query sampler
departs in two ways, both so that a pool covers the traffic's
distribution evenly; each query is still one draw from it:

* every batch of uniform draws is stratified — draw ``i`` of ``n`` is
  ``(perm[i] + u_i) / n`` — so each draw keeps its exact marginal while
  the set of draws covers the distribution evenly;
* the arity of each query and whether a companion term is topical are
  dealt out in exact proportions, shuffled, instead of drawn one by one.

The benchmark compares medians over runs with different seeds, so every
seed serves the same work: one pool of queries, drawn from the
configuration's seed, in an order drawn from the run's seed
(:func:`serve_order`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

QUERY_PAD = -1


@dataclasses.dataclass(frozen=True)
class CorpusSpec:
    """Parameters of a synthetic corpus (the configuration's ``corpus``)."""

    n_docs: int
    n_terms: int
    mean_doc_len: float
    sigma_doc_len: float
    zipf_s: float
    n_topics: int
    topicality: float
    topic_boost: float
    topic_block_lo: int
    topic_block_hi: Optional[int]
    seed: int


@dataclasses.dataclass
class Corpus:
    """CSR set-of-terms corpus: ``doc_terms[doc_ptr[d]:doc_ptr[d+1]]`` is
    the sorted set of distinct term ids of document ``d``."""

    doc_ptr: np.ndarray  # (n_docs + 1,) int64
    doc_terms: np.ndarray  # (nnz,) int32
    n_terms: int
    doc_topic: Optional[np.ndarray] = None
    spec: Optional[CorpusSpec] = None

    @property
    def n_docs(self) -> int:
        return len(self.doc_ptr) - 1

    @property
    def nnz(self) -> int:
        return int(self.doc_ptr[-1])

    def term_doc_freq(self) -> np.ndarray:
        return np.bincount(self.doc_terms, minlength=self.n_terms)


def _zipf_probs(n_terms: int, s: float) -> np.ndarray:
    ranks = np.arange(1, n_terms + 1, dtype=np.float64)
    p = ranks**-s
    return p / p.sum()


def synth_corpus(spec: CorpusSpec) -> Corpus:
    """Zipf marginals with latent topic blocks; deterministic in ``spec.seed``."""
    rng = np.random.default_rng(spec.seed)
    n, m = spec.n_docs, spec.n_terms

    base_p = _zipf_probs(m, spec.zipf_s)

    hi = spec.topic_block_hi if spec.topic_block_hi is not None else m // 2
    lo = min(spec.topic_block_lo, hi - 1)
    block = max(1, (hi - lo) // max(spec.n_topics, 1))
    topic_p = np.tile(base_p, (spec.n_topics, 1))
    for z in range(spec.n_topics):
        b0 = lo + z * block
        b1 = min(lo + (z + 1) * block, hi)
        topic_p[z, b0:b1] *= spec.topic_boost
    topic_p /= topic_p.sum(axis=1, keepdims=True)

    mu = np.log(spec.mean_doc_len) - 0.5 * spec.sigma_doc_len**2
    lengths = np.maximum(
        2, rng.lognormal(mean=mu, sigma=spec.sigma_doc_len, size=n).astype(np.int64)
    )
    doc_topic = rng.integers(0, spec.n_topics, size=n)

    total = int(lengths.sum())
    tok_doc = np.repeat(np.arange(n), lengths)

    from_topic = rng.random(total) < spec.topicality
    u = rng.random(total)
    base_cdf = np.cumsum(base_p)
    tokens = np.empty(total, dtype=np.int64)
    glob = ~from_topic
    tokens[glob] = np.searchsorted(base_cdf, u[glob], side="right")
    topic_cdf = np.cumsum(topic_p, axis=1)
    # The program loops over topics with a full-length mask each time; one
    # stable sort by topic visits the same tokens in the same order.
    tok_topic = doc_topic[tok_doc]
    sel = np.flatnonzero(from_topic)
    sel = sel[np.argsort(tok_topic[sel], kind="stable")]
    bounds = np.searchsorted(tok_topic[sel], np.arange(spec.n_topics + 1))
    for z in range(spec.n_topics):
        idx = sel[bounds[z] : bounds[z + 1]]
        if len(idx):
            tokens[idx] = np.searchsorted(topic_cdf[z], u[idx], side="right")
    np.clip(tokens, 0, m - 1, out=tokens)

    key = tok_doc * np.int64(m) + tokens
    key = np.unique(key)
    out_doc = (key // m).astype(np.int64)
    out_term = (key % m).astype(np.int32)
    counts = np.bincount(out_doc, minlength=n)
    doc_ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=doc_ptr[1:])
    return Corpus(doc_ptr=doc_ptr, doc_terms=out_term, n_terms=m, doc_topic=doc_topic, spec=spec)


@dataclasses.dataclass
class QueryLog:
    """Padded ``(n_queries, max_arity)`` int32 term ids, ``QUERY_PAD``-filled."""

    queries: np.ndarray

    @property
    def n_queries(self) -> int:
        return len(self.queries)

    def arities(self) -> np.ndarray:
        return (self.queries != QUERY_PAD).sum(axis=1)

    def term_lists(self) -> list:
        return [row[row != QUERY_PAD].tolist() for row in self.queries]


def _stratified(rng: np.random.Generator, size: int) -> np.ndarray:
    """``size`` uniforms on [0, 1), one in each of ``size`` equal strata,
    in random order."""
    return (rng.permutation(size) + rng.random(size)) / max(size, 1)


def _dealt(rng: np.random.Generator, values: np.ndarray, weights: np.ndarray, size: int):
    """``size`` picks of ``values`` in exact proportion to ``weights``
    (largest remainder), shuffled."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    raw = w * size
    n = np.floor(raw).astype(np.int64)
    short = size - int(n.sum())
    n[np.argsort(-(raw - n), kind="stable")[:short]] += 1
    out = np.repeat(np.asarray(values), n)
    rng.shuffle(out)
    return out


def synth_query_log(
    corpus: Corpus,
    n_queries: int,
    zipf_s: float,
    co_topic: float,
    frequency_weight: float,
    seed,
    arity: Sequence[int],
    arity_weights: Sequence[float],
) -> QueryLog:
    """Zipf-like conjunctive queries against ``corpus``: term propensity
    mixes document frequency with a Zipf tilt over frequency rank, and
    non-leading terms come from the leading term's topic block with
    probability ``co_topic``.  Terms within a query are distinct and
    every term occurs in the corpus."""
    rng = np.random.default_rng(seed)
    df = corpus.term_doc_freq().astype(np.float64)
    alive = df > 0
    order = np.argsort(-df, kind="stable")
    rank = np.empty_like(order)
    rank[order] = np.arange(1, len(order) + 1)
    prop = np.where(
        alive,
        (df + 1e-9) ** frequency_weight
        * rank.astype(np.float64) ** (-zipf_s * (1.0 - frequency_weight)),
        0.0,
    )
    prop /= prop.sum()
    cdf = np.cumsum(prop)

    def draw(size: int) -> np.ndarray:
        t = np.searchsorted(cdf, _stratified(rng, size), side="right")
        return np.minimum(t, len(cdf) - 1).astype(np.int64)

    spec = corpus.spec
    hi = spec.topic_block_hi if spec.topic_block_hi is not None else corpus.n_terms // 2
    lo = min(spec.topic_block_lo, hi - 1)
    blockw = max(1, (hi - lo) // max(spec.n_topics, 1))

    def topical(t: np.ndarray) -> np.ndarray:
        n = len(t)
        u = draw(n)
        if co_topic > 0:
            same = _dealt(rng, np.array([True, False]), np.array([co_topic, 1 - co_topic]), n)
            in_block = same & (t >= lo) & (t < lo + blockw * spec.n_topics)
            if in_block.any():
                z = (t[in_block] - lo) // blockw
                off = rng.integers(0, blockw, size=int(in_block.sum()))
                u2 = np.minimum(lo + z * blockw + off, corpus.n_terms - 1)
                ok = df[u2] > 0
                u[np.flatnonzero(in_block)[ok]] = u2[ok]
        return u

    arities = np.asarray(arity, np.int64)
    max_arity = int(arities.max())
    t = draw(n_queries)
    per_query = _dealt(rng, arities, np.asarray(arity_weights), n_queries)
    q = np.full((n_queries, max_arity), QUERY_PAD, dtype=np.int64)
    q[:, 0] = t
    for slot in range(1, max_arity):
        idx = np.flatnonzero(per_query > slot)
        if not len(idx):
            break
        u = topical(t[idx])
        dup = (q[idx, :slot] == u[:, None]).any(axis=1)
        while dup.any():
            u[dup] = draw(int(dup.sum()))
            dup = (q[idx, :slot] == u[:, None]).any(axis=1)
        q[idx, slot] = u
    return QueryLog(queries=q.astype(np.int32))


def serve_order(cost: np.ndarray, batch: int, passes: int, seed) -> np.ndarray:
    """The order in which a closed loop serves a fixed pool: ``passes``
    passes over the pool, each in a fresh order drawn from ``seed``.

    The pool, sorted by ``cost``, is cut into ``batch`` strata of equal
    size; each run of ``batch`` consecutive positions holds one query of
    every stratum.  So every batch the loop seals holds the same spread of
    work, and a window that ends part-way through a pass has still served
    the pool's mix.  Returns pool indices, ``passes * len(cost)`` of them."""
    n = len(cost)
    if n % batch:
        raise ValueError(f"a pool of {n} queries is not a whole number of batches of {batch}")
    strata = np.argsort(np.asarray(cost), kind="stable").reshape(batch, n // batch)
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(passes):
        members = rng.permuted(strata, axis=1).T  # batch j: member j of each stratum
        members = rng.permuted(members, axis=1)
        out.append(members[rng.permutation(len(members))].ravel())
    return np.concatenate(out)
