"""The plain reference: conjunctive counts straight from the corpus.

Per-term document lists are built from the corpus's own term sets with
one stable sort (no index code, nothing the program made), and each
query's count is the size of the intersection of its terms' lists.  The
program answers in its own document order after clustering; a count does
not depend on the order, so none is needed here.

``control_bits`` computes the same answers with document ids held in
fewer bits (16 for the configurations' int32 ids): the step below the
stated precision.  Put in the program's place (``bench/run.py
--control``), it is the control that a sound comparison has to fail.
"""

from __future__ import annotations

import numpy as np


class TermLists:
    """Sorted document lists of the terms that ``queries`` use."""

    def __init__(self, corpus, terms):
        need = np.unique(np.asarray(terms, np.int64))
        doc_of = np.repeat(np.arange(corpus.n_docs, dtype=np.int32), np.diff(corpus.doc_ptr))
        hit = np.isin(corpus.doc_terms, need)
        term, doc = corpus.doc_terms[hit], doc_of[hit]
        order = np.argsort(term, kind="stable")  # docs stay ascending per term
        term, doc = term[order], doc[order]
        lo = np.searchsorted(term, need, side="left")
        hi = np.searchsorted(term, need, side="right")
        self._lists = {int(t): doc[a:b] for t, a, b in zip(need, lo, hi, strict=True)}
        self._cut = {}

    def _list(self, t: int, control_bits: int) -> np.ndarray:
        if not control_bits:
            return self._lists[t]
        key = (t, control_bits)
        if key not in self._cut:
            self._cut[key] = np.unique(self._lists[t] & ((1 << control_bits) - 1))
        return self._cut[key]

    def docs(self, terms, control_bits: int = 0) -> np.ndarray:
        """Documents holding every term, shortest list first.  With
        ``control_bits`` the ids are first cut to that many bits."""
        lists = sorted((self._list(int(t), control_bits) for t in terms), key=len)
        out = lists[0]
        for other in lists[1:]:
            if not len(out):
                break
            pos = np.minimum(np.searchsorted(other, out), len(other) - 1)
            out = out[other[pos] == out] if len(other) else out[:0]
        return out

    def count(self, terms, control_bits: int = 0) -> int:
        return len(self.docs(terms, control_bits))


def reference_counts(corpus, term_lists, control_bits: int = 0) -> np.ndarray:
    """Exact count of every query in ``term_lists`` (a list of term lists)."""
    ref = TermLists(corpus, [t for q in term_lists for t in q])
    return np.array([ref.count(q, control_bits) for q in term_lists], np.int64)
