"""Spans of the serving path on the profiler's clock.

Invariants under test: a traced serving loop records one ``seclud.batch``
span per batch, each carrying its ordinal, with ``seclud.seal``, the
engine's ``seclud.plan`` / ``lower`` / ``upload`` / ``dispatch`` /
``readback`` and ``seclud.reply`` nested inside it in that order; the
``t_*_s`` keys of ``info`` are the durations of those same spans; both
device paths count the padded cells they carry and the bytes they
upload, and the cells the stages after the first carry; and the fold's
device ops carry stable ``seclud.fold/...`` scope names.
"""

import asyncio
import contextlib
import gc
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.analysis.runtime import span
from repro.core.batched_query import plan_segment_pairs
from repro.core.device_engine import (
    _fused_fold,
    device_counts,
    device_index,
    lower_plan,
    lower_plan_sharded,
    sharded_device_counts,
    sharded_device_index,
)
from repro.core.queries import as_queries
from repro.core.hier_index import as_hier
from repro.core.seclud import SecludPipeline
from repro.data.query_log import synth_query_log
from repro.serve.loop import AsyncServingLoop, ServeConfig
from repro.serve.search_service import SearchService

ENGINE_SPANS = ("seclud.plan", "seclud.lower", "seclud.upload", "seclud.dispatch",
                "seclud.readback")
BATCH_SPANS = ("seclud.seal",) + ENGINE_SPANS + ("seclud.reply",)


@pytest.fixture(scope="module")
def service(small_corpus, small_log):
    pipe = SecludPipeline(tc=800, doc_grained_below=256, seed=0)
    return SearchService(pipe.fit(small_corpus, k=12, algo="topdown", log=small_log))


@pytest.fixture(scope="module")
def queries(small_corpus):
    """Arities 1-3, so the fold runs two chain stages."""
    log = synth_query_log(small_corpus, n_queries=96, seed=5, arity=(1, 2, 3),
                          arity_weights=(0.2, 0.4, 0.4))
    return log.queries


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; return its result and the host's
    ``seclud.*`` spans as ``(name, start_ns, end_ns, args)``, by start."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("seclud."):
                    spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                  dict(e.stats)))
    return out, sorted(spans, key=lambda s: s[1])


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_span_adds_its_duration():
    info = {}
    with span("seclud.test", info, "t_s", arg=1):
        pass
    first = info["t_s"]
    assert first >= 0.0
    with span("seclud.test", info, "t_s"):
        sum(range(1000))
    assert info["t_s"] > first  # spans sharing a key sum
    with span("seclud.test"):  # no key: a trace span only
        pass
    assert set(info) == {"t_s"}


def test_traced_loop_records_every_span_per_batch(service, queries, tmp_path):
    loop = AsyncServingLoop(service, ServeConfig(max_batch=16, deadline_s=0.001))

    async def session():
        await loop.start()
        out = await asyncio.gather(*(loop.submit(q[q >= 0].tolist()) for q in queries))
        await loop.stop()
        return out

    counts, spans = _traced(tmp_path, lambda: asyncio.run(session()))
    assert counts == service.serve_counts_device(queries)[0].tolist()
    batches = [s for s in spans if s[0] == "seclud.batch"]
    assert len(batches) == loop.stats.n_batches >= 6
    assert [b[3]["batch"] for b in batches] == list(range(len(batches)))
    assert [b[3]["size"] for b in batches] == loop.stats.batch_sizes
    assert [b[3]["queue_depth"] for b in batches] == loop.stats.queue_depths
    for b in batches:
        inner = [s for s in spans if s[0] != "seclud.batch" and _inside(s, b)]
        # One span of each kind per batch, nested in the batch, in order.
        assert [s[0] for s in inner] == list(BATCH_SPANS)
        for a, c in zip(inner, inner[1:]):
            assert a[2] <= c[1]
    # No span of the serving path falls outside a batch.
    assert sum(any(_inside(s, b) for b in batches) for s in spans) == len(spans)


@pytest.mark.parametrize("sharded", [False, True])
def test_info_counts_cells_and_upload_bytes(service, queries, sharded, tmp_path):
    hidx = service.query_index
    cq = as_queries(queries[:40])
    plan = plan_segment_pairs(device_index(hidx).host, cq, track_work=False)
    if sharded:
        sidx = sharded_device_index(hidx, n_shards=2)
        low = lower_plan_sharded(plan, sidx)
        (counts, info), spans = _traced(
            tmp_path, lambda: sharded_device_counts(hidx, cq, sidx=sidx))
        assert info["cells"] == 2 * low.n_cells
        assert info["cells_true"] == low.n_cells_true.sum()
    else:
        low = lower_plan(plan)
        (counts, info), spans = _traced(tmp_path, lambda: device_counts(hidx, cq))
        assert info["cells"] == low.n_cells
        assert info["cells_true"] == low.n_cells_true
    assert info["cells"] >= info["cells_true"] > 0
    assert info["upload_bytes"] == low.cells.nbytes + low.stage_seg.nbytes
    # The cells stages 2 on carry, and of those the ones still live.
    late = low.stage_cells[1:] if sharded else low.cell_prefix[1:]
    assert info["late_cells"] == sum(late) > 0
    assert 0 <= info["late_live"] <= info["late_cells"]
    assert info["late_live"] == sum(s["cur_live"] for s in info["stages"][1:])
    assert counts.tolist() == service.serve_counts(cq)[0].tolist()

    by_name = {s[0]: s for s in spans}
    assert set(by_name) == set(ENGINE_SPANS)
    # Each t_*_s is its span's duration; t_fold_s runs from the upload's
    # start to the readback's end (the spans abut).
    def dur(name):
        return (by_name[name][2] - by_name[name][1]) * 1e-9

    assert info["t_plan_s"] == pytest.approx(dur("seclud.plan"), abs=2e-4)
    assert info["t_lower_s"] == pytest.approx(dur("seclud.lower"), abs=2e-4)
    fold_interval = (by_name["seclud.readback"][2] - by_name["seclud.upload"][1]) * 1e-9
    assert info["t_fold_s"] == pytest.approx(fold_interval, abs=5e-4)
    assert info["t_fold_s"] <= fold_interval


@contextlib.contextmanager
def _slow_collections():
    """A garbage collection of about 1 ms due at every allocation."""
    def slow(phase, _info):
        if phase == "start":
            time.sleep(0.001)

    threshold = gc.get_threshold()
    gc.callbacks.append(slow)
    gc.set_threshold(1)
    try:
        yield
    finally:
        gc.set_threshold(*threshold)
        gc.callbacks.remove(slow)


@pytest.mark.parametrize("sharded", [False, True])
def test_span_times_hold_through_garbage_collections(service, queries, sharded, tmp_path):
    """A collection the interpreter runs inside a span, at its edges or
    between the fold's three spans counts in the trace's interval and in
    ``info`` alike: with one due at every allocation, each ``t_*_s``
    still equals its span's duration."""
    hidx = service.query_index
    cq = as_queries(queries[40:80])
    if sharded:
        sidx = sharded_device_index(hidx, n_shards=2)
        call = lambda: sharded_device_counts(hidx, cq, sidx=sidx)  # noqa: E731
    else:
        call = lambda: device_counts(hidx, cq)  # noqa: E731
    call()  # compile outside the measured call
    with _slow_collections():
        (_counts, info), spans = _traced(tmp_path, call)
    by_name = {s[0]: s for s in spans}
    for key, name in (("t_plan_s", "seclud.plan"), ("t_lower_s", "seclud.lower")):
        assert info[key] == pytest.approx((by_name[name][2] - by_name[name][1]) * 1e-9,
                                          abs=2e-4), key
    fold_interval = (by_name["seclud.readback"][2] - by_name["seclud.upload"][1]) * 1e-9
    assert info["t_fold_s"] == pytest.approx(fold_interval, abs=2e-4)


def test_empty_batch_info_counts_nothing(service):
    hidx = service.query_index
    df = np.diff(as_hier(hidx).index.post_ptr)
    absent = int(np.flatnonzero(df == 0)[0])
    for fn in (device_counts, sharded_device_counts):
        counts, info = fn(hidx, np.array([[absent, absent]]))
        assert counts.tolist() == [0]
        assert info["cells"] == info["cells_true"] == info["upload_bytes"] == 0.0
        assert info["late_cells"] == info["late_live"] == 0.0


def test_fold_ops_carry_stable_scope_names(service, queries):
    """The compiled fold's op metadata names each stage's search and its
    bounds gather, the first gather and the count under ``seclud.fold``."""
    dindex = device_index(service.query_index)
    cq = as_queries(queries[:40])
    low = lower_plan(plan_segment_pairs(dindex.host, cq, track_work=False))
    assert len(low.stage_levels) >= 2
    text = _fused_fold.lower(
        dindex.post_docs, dindex.fences, low.cells, low.stage_seg,
        group_width=low.group_width,
        stage_levels=low.stage_levels, n_queries_pad=low.n_queries_pad,
        return_members=False,
    ).compile().as_text()
    for scope in ["seclud.fold/gather", "seclud.fold/count"] + [
            f"seclud.fold/stage{s + 1}/{sub}" for s in range(len(low.stage_levels))
            for sub in ("", "bounds/")]:
        assert scope in text, scope
    # The stage loops are the segment searches: each while op is scoped.
    whiles = [ln for ln in text.splitlines() if " while(" in ln]
    assert whiles and all("seclud.fold/stage" in ln for ln in whiles)
