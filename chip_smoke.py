#!/usr/bin/env python3
"""Bring-up smoke of the exact-search service on a TPU.

Drives the serving path once through the entry points a user calls —
``synth_corpus`` -> ``SecludPipeline.fit`` -> ``SearchService`` ->
``serve_counts_device`` / ``replay`` — at a corpus size users would call
real (default 1M wiki-like documents, about 100M postings resident on the
chip), and checks every answer bit for bit against the host engine and an
independent ``np.intersect1d`` reference over the corpus's own term lists.

    python3 chip_smoke.py                  # one chip: every phase
    python3 chip_smoke.py --four-chips     # sharded serving over 4 chips
    JAX_PLATFORMS=cpu python3 chip_smoke.py --n-docs 5000   # rehearsal

Every line but the last is information.  The last line is one JSON object,
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``,
with the device as JAX reports it.  ``ok`` is true only when every phase
passed on a TPU; a failed phase exits non-zero.  Off the TPU the phases run
only when ``--n-docs`` is given (a rehearsal at a small size) and the
result is never ok.  Without the repo's ``src/`` beside it, the script
exits non-zero before it imports JAX and prints no result.

Everything runs in this one process, which holds the chip(s) throughout.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARITIES = (2, 3, 5)
DEFAULT_N_DOCS = 1_000_000
N_QUERIES = 2048  # the replayed log
# Offered rate of the replay: at 2 ms deadline most sealed batches fill
# toward max_batch, which keeps the prewarm grid (one compile per shape
# key) to a few dozen keys.
QPS = 20_000.0


def log(msg: str) -> None:
    print(msg, flush=True)


class Smoke:
    """Shared state of the phases; each phase raises on a failed check."""

    def __init__(self, args, on_tpu: bool):
        self.args = args
        self.on_tpu = on_tpu

    # -- set-up ------------------------------------------------------------

    def data(self):
        from repro.data.corpus import CorpusSpec, synth_corpus
        from repro.data.query_log import synth_query_log

        a = self.args
        self.corpus = synth_corpus(CorpusSpec.wiki_like(n_docs=a.n_docs, seed=a.seed))
        self.log = synth_query_log(
            self.corpus,
            n_queries=N_QUERIES,
            seed=a.seed + 1,
            arity=ARITIES,
            arity_weights=(0.5, 0.3, 0.2),
            arrival_qps=QPS,
        )
        self.cq = self.log.as_conjunctive()
        log(
            f"data: documents={self.corpus.n_docs} postings={self.corpus.nnz} "
            f"queries={self.cq.n_queries} "
            f"arities={ {a: int((self.cq.arities == a).sum()) for a in ARITIES} }"
        )

    def fit(self):
        import jax

        from repro.core.seclud import SecludPipeline
        from repro.serve.search_service import SearchService

        pipe = SecludPipeline(tc=2000, seed=self.args.seed)
        self.res = pipe.fit(self.corpus, k=64, algo="topdown", log=self.log, levels=2)
        self.svc = SearchService(self.res)
        dindex = self.svc.device_index
        dev = next(iter(dindex.post_docs.devices()))
        _check(
            dev.platform == jax.devices()[0].platform,
            f"post_docs live on {dev.platform}, not the default backend",
        )
        log(
            f"fit: clusters={self.res.k} post_docs_bytes={int(dindex.post_docs.nbytes)} "
            f"resident_index_bytes={dindex.nbytes} on {dev}"
        )
        stats = dev.memory_stats() or {}
        if "bytes_in_use" in stats:
            log(f"fit: device bytes_in_use={stats['bytes_in_use']}")

    # -- exactness -----------------------------------------------------------

    def batches(self, size: int, n_batches: int):
        """``n_batches`` windows of ``size`` queries of each arity."""
        arities = self.cq.arities
        for a in ARITIES:
            ids = np.flatnonzero(arities == a)
            for b in range(n_batches):
                sel = ids[b * size : (b + 1) * size]
                if len(sel):
                    yield a, self.cq.from_lists([self.cq.terms(int(q)) for q in sel])

    def exactness(self):
        from repro.core.batched_query import batched_query

        batches = list(self.batches(64, 3))
        n_check = 32  # queries per batch held to the intersect1d reference
        ref = _TermLists(
            self.corpus,
            self.res.perm,
            np.concatenate([qb.terms(q) for _, qb in batches for q in range(min(qb.n_queries, n_check))]),
        )
        n_batches = n_ref = 0
        for a, qb in batches:
            counts, docs, _info = self.svc.serve_counts_device(qb, return_docs=True)
            ptr, hdocs, _work = batched_query(self.svc.query_index, qb)
            _check(np.array_equal(counts, np.diff(ptr)), f"arity {a}: device counts != host")
            _check(np.array_equal(docs, hdocs), f"arity {a}: device docs != host")
            dptr = np.concatenate([[0], np.cumsum(counts)])
            for q in range(min(qb.n_queries, n_check)):
                want = ref.intersect(qb.terms(q))
                got = docs[dptr[q] : dptr[q + 1]]
                _check(np.array_equal(got, want), f"arity {a}: query {q} != intersect1d")
                n_ref += 1
            n_batches += 1
        _check(n_ref >= 64, f"only {n_ref} queries met the intersect1d reference")
        log(
            f"exactness: {n_batches} batches at arities {ARITIES} bit-identical to host "
            f"batched_query (counts and docs); {n_ref} queries == np.intersect1d reference"
        )

    def packed(self):
        import jax

        from repro.core.batched_query import batched_query
        from repro.kernels.intersect.ops import intersect_count, intersect_members

        for a, qb in self.batches(16, 1):
            packed = self.svc.pack(qb)
            got = np.asarray(self.svc.device_counts(packed))
            ptr, _docs, _work = batched_query(self.svc.query_index, qb)
            _check(np.array_equal(got, np.diff(ptr)), f"arity {a}: packed counts != host")
            short, long = packed.segments[0], packed.segments[1]
            op = (
                intersect_count
                if a == 2
                else lambda s, l: intersect_members(s, l, reduce="mask")
            )
            # The lowered program of the op the packed path just ran on
            # these shapes: a Mosaic kernel shows up as a tpu_custom_call.
            kernel = "tpu_custom_call" in jax.jit(op).lower(short, long).as_text()
            if self.on_tpu:
                _check(kernel, f"arity {a}: packed path did not run the Pallas kernel")
            log(
                f"packed: arity {a} rows={short.shape[0]} widths="
                f"{[s.shape[1] for s in packed.segments]} counts == host; "
                f"Pallas kernel (tpu_custom_call) in lowered HLO: {kernel}"
            )

    # -- serving -------------------------------------------------------------

    def serving(self):
        from repro.core.device_engine import prewarm
        from repro.serve.loop import ServeConfig, plan_batches
        from repro.serve.replay import replay

        cfg = ServeConfig(max_batch=64, deadline_s=0.002)
        batches = plan_batches(self.log.arrivals, cfg.max_batch, cfg.deadline_s)
        t0 = time.perf_counter()
        pw = prewarm(
            self.svc.query_index, self.cq, batches=batches, dindex=self.svc.device_index
        )
        prewarm_s = time.perf_counter() - t0
        log(f"serving: prewarm keys={pw['n_keys']} compiles={pw['n_compiles']} seconds={prewarm_s:.3f}")

        infos = []

        def engine(queries):
            out = self.svc.serve_counts_device(queries)
            infos.append(out[-1])
            return out

        # No resilience ladder: a device failure raises instead of being
        # served by the host rung.
        rep = replay(self.svc, self.log, config=cfg, mode="sealed", engine=engine)
        _check(rep.jit_compiles == 0, f"steady state compiled {rep.jit_compiles}x after prewarm")
        levels = rep.summary()["levels"]
        _check(set(levels) == {"device"}, f"batches served off the device: {levels}")
        direct, _ = self.svc.serve_counts_device(self.cq)
        _check(np.array_equal(rep.counts, direct), "replay counts != direct dispatch")
        med = {
            k: float(np.median([i[k] for i in infos]))
            for k in ("t_plan_s", "t_lower_s", "t_fold_s")
        }
        log(
            f"serving: replay queries={self.cq.n_queries} batches={len(rep.batches)} "
            f"jit_compiles={rep.jit_compiles} levels={levels} counts == direct dispatch"
        )
        log(
            "serving: median per batch (information, not a benchmark) "
            + " ".join(f"{k}={v:.6f}" for k, v in med.items())
        )

    # -- four chips ----------------------------------------------------------

    def sharded(self):
        import jax

        from repro.core.batched_query import batched_query
        from repro.core.device_engine import device_counts

        _check(len(jax.devices()) >= 4, f"--four-chips needs 4 devices, found {len(jax.devices())}")
        batches = list(self.batches(64, 2))
        want, fold_s = [], []
        for a, qb in batches:
            single = device_counts(
                self.svc.query_index, qb, dindex=self.svc.device_index, return_docs=True
            )
            ptr, hdocs, _work = batched_query(self.svc.query_index, qb)
            _check(np.array_equal(single[0], np.diff(ptr)), f"arity {a}: single-device counts != host")
            _check(np.array_equal(single[1], hdocs), f"arity {a}: single-device docs != host")
            want.append((a, qb, single[0], single[1]))
            fold_s.append(single[2]["t_fold_s"])
        log(f"sharded: single-device fold median t_fold_s={float(np.median(fold_s)):.6f} (information, includes compiles)")

        sidx = self.svc.enable_sharded(n_shards=4, strikes_to_evict=1)
        shard_devs = [s.device for s in sidx.post_docs.addressable_shards]
        _check(len({d.id for d in shard_devs}) == 4, f"postings not on 4 devices: {shard_devs}")
        log(f"sharded: postings {sidx.post_docs.shape} over {sorted(d.id for d in shard_devs)}")
        self._compare_sharded(want, 4)

        times = [1.0] * self.svc.n_shards
        times[-1] = 100.0
        _verdicts, remeshed = self.svc.record_shard_times(times)
        _check(remeshed and self.svc.n_shards == 3, "forced eviction did not remesh to 3 shards")
        shard_devs = [s.device for s in self.svc.sharded_index.post_docs.addressable_shards]
        log(f"sharded: evicted one shard; postings over {sorted(d.id for d in shard_devs)}")
        self._compare_sharded(want, 3)

    def _compare_sharded(self, want, n_shards: int):
        fold_s = []
        for a, qb, counts, docs in want:
            c, d, info = self.svc.serve_counts_device(qb, return_docs=True)
            fold_s.append(info["t_fold_s"])
            _check(info["n_shards"] == n_shards, f"served by {info['n_shards']} shards")
            _check(np.array_equal(c, counts), f"{n_shards} shards, arity {a}: counts differ")
            _check(np.array_equal(d, docs), f"{n_shards} shards, arity {a}: docs differ")
        log(
            f"sharded: {n_shards} shards, {len(want)} batches at arities {ARITIES} "
            "bit-identical to the single-device fold and the host engine; "
            f"median t_fold_s={float(np.median(fold_s)):.6f} (information, includes compiles)"
        )


class _TermLists:
    """Per-term document lists straight from the corpus (no index code)
    for the terms a sample of queries uses: the plain reference each
    conjunctive query is checked against, in the fitted doc-id order."""

    def __init__(self, corpus, perm, terms):
        self.perm = perm
        need = np.unique(np.asarray(terms, np.int64))
        doc_of = np.repeat(np.arange(corpus.n_docs, dtype=np.int32), np.diff(corpus.doc_ptr))
        hit = np.isin(corpus.doc_terms, need)
        term, doc = corpus.doc_terms[hit], doc_of[hit]
        order = np.argsort(term, kind="stable")  # docs stay ascending per term
        term, doc = term[order], doc[order]
        lo = np.searchsorted(term, need, side="left")
        hi = np.searchsorted(term, need, side="right")
        self._lists = {int(t): doc[a:b] for t, a, b in zip(need, lo, hi, strict=True)}

    def intersect(self, terms) -> np.ndarray:
        out = self._lists[int(terms[0])]
        for t in terms[1:]:
            out = np.intersect1d(out, self._lists[int(t)])
        return np.sort(self.perm[out]).astype(np.int32)


def _check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n-docs", type=int, default=None, help=f"corpus size (default {DEFAULT_N_DOCS})")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true", help="run only the 4-chip sharded phase")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro beside {__file__}; run it from the repo", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    from repro.compile_cache import cache_stats, enable_compile_cache

    cache_dir = enable_compile_cache(ROOT)

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    on_tpu = device["platform"] == "tpu"
    log(f"device: {device} jax={jax.__version__} compile cache at {cache_dir}")

    def finish(ok: bool) -> int:
        print(json.dumps({"ok": bool(ok and on_tpu), "device": device}), flush=True)
        return 0 if ok and on_tpu else 1

    if not on_tpu and args.n_docs is None:
        log("no TPU found; pass --n-docs to rehearse every phase here at a small size")
        return finish(False)
    if args.n_docs is None:
        args.n_docs = DEFAULT_N_DOCS

    smoke = Smoke(args, on_tpu)
    phases = ["data", "fit"]
    phases += ["sharded"] if args.four_chips else ["exactness", "packed", "serving"]
    ok = True
    for name in phases:
        t0 = time.perf_counter()
        try:
            getattr(smoke, name)()
        except Exception:
            traceback.print_exc()
            log(f"phase {name}: FAILED after {time.perf_counter() - t0:.3f} s")
            ok = False
            break
        log(f"phase {name}: ok in {time.perf_counter() - t0:.3f} s")
    stats = cache_stats()
    log(
        f"compile cache: hits={stats['hits']} writes={stats['misses']} "
        f"({'hit' if stats['hits'] else 'no hit'} this run)"
    )
    return finish(ok)


if __name__ == "__main__":
    sys.exit(main())
