"""The bytes an exact conjunctive count has to move: the fold's roofline model.

Counted from the corpus and the queries alone, through the plain
reference, never from how the program plans or lays out a batch, so no
change to the program can move it.  For a query of ``arity`` terms whose
exact answer is ``count`` documents:

* every answer has to be found in each of the query's posting lists, so
  at least one 4-byte posting of each list is read per answer;
* one 4-byte count is written back.

Documents that are not answers can be ruled out without reading their
postings (a clustered index skips whole clusters), so they add nothing.
The result is a lower bound on what any exact algorithm over posting
lists moves: bytes over peak bandwidth over the fold's device time cannot
honestly pass 100%.
"""

from __future__ import annotations

import numpy as np

POSTING_BYTES = 4
COUNT_BYTES = 4


def needed_bytes(arities: np.ndarray, counts: np.ndarray) -> float:
    """Bytes that answering queries of ``arities`` with exact answers
    ``counts`` (the reference's) must read and write, summed."""
    arities = np.asarray(arities, np.float64)
    counts = np.asarray(counts, np.float64)
    return float(POSTING_BYTES * (arities * counts).sum() + COUNT_BYTES * len(counts))
