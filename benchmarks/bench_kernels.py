"""Wall-clock of the batched-intersection paths and the SeCluD search
service's packed device path (``device/seclud_packed``) against baseline
single-index execution.  On a TPU that row runs the compiled Pallas
intersect kernels; elsewhere it runs the jnp reference, and the numbers
are CPU sanity checks, not device numbers.  No roofline of the kernels
exists yet."""

import numpy as np

from benchmarks.common import corpus_and_log, row, timed
from repro.core.seclud import SecludPipeline
from repro.index.batched import batch_queries, count_intersections_jnp
from repro.serve.search_service import SearchService


def run(quick: bool = True):
    n_docs = 10000 if quick else 40000
    corpus, log = corpus_and_log("forum", n_docs)
    pipe = SecludPipeline(tc=3000, doc_grained_below=512)
    res = pipe.fit(corpus, 128, algo="topdown", log=log)
    queries = log.queries[:256]

    rows = []
    # Baseline: batched single-index intersection (padded bins).
    batched = batch_queries(res.base_index, queries)
    def run_baseline():
        total = 0
        for b in batched.bins:
            total += int(count_intersections_jnp(b.short, b.long).sum())
        return total
    n_base, t_base = timed(run_baseline, repeats=3)
    rows.append(
        row("device/baseline_batched", t_base,
            f"hits={n_base};pad_overhead={batched.padding_overhead():.2f}")
    )

    # SeCluD: cluster-routed segments (smaller padded problems).
    svc = SearchService(res)
    packed = svc.pack(queries)
    def run_clustered():
        return int(np.asarray(SearchService.device_counts(packed)).sum())
    n_clus, t_clus = timed(run_clustered, repeats=3)
    rows.append(
        row("device/seclud_packed", t_clus,
            f"hits={n_clus};rows={packed.short.shape};speedup={t_base / max(t_clus, 1e-9):.2f}")
    )
    assert n_base == n_clus, "lossless violation in device paths"
    return rows
