#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload wiki.closed --seed 7 --seconds 30 --trace 0
    JAX_PLATFORMS=cpu python3 bench/run.py --workload wiki.closed --seed 7 \\
        --seconds 3 --trace 1 --rehearse        # tiny sizes on the CPU

A cell ``<config>.<traffic>`` is found by name: ``BENCHMARK.json`` names
its configuration file and chips, ``bench/traffic/<traffic>.json`` holds
the traffic's parameters, and each metric is read by
``bench/metrics/<metric>.py``.  The run builds the configuration's
corpus and its query pool from its ``data_seed``, fits and indexes it
with the program, orders the pool into a stream from ``--seed``,
prewarms the shapes that stream
produces, drives the program's serving loop for ``--seconds`` on the
wall clock, and then checks every answer against the plain reference in
``bench/reference.py``.  ``--control`` puts that reference, at 16-bit
document ids, in the program's place: such a run has to read
``correct: false``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its
limit.  The same checks are the last lines of standard error.  Without a
TPU (or with fewer chips than the cell asks for) the run exits non-zero
and prints no result; ``--rehearse`` runs the same path on the CPU at the
configuration's rehearsal size, and its device says so.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"
SEED_MOD = 2**63 - 1
CONTROL_BITS = 16  # the configurations state int32 document ids


class NoDevice(RuntimeError):
    """No measurement is possible on this machine."""


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_metric(name: str) -> Callable:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def find_cell(name: str, rehearse: bool) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    if rehearse:
        config = {**config, **config["rehearsal"]}
        traffic = {**traffic, **traffic.get("rehearsal", {})}

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


# -- the record the metric readers see -------------------------------------


@dataclasses.dataclass
class Record:
    """Everything one run measured; ``bench/metrics/*.py`` read from it."""

    setup_s: float
    t0: float  # window start (perf_counter seconds)
    t1: float  # window end
    requests: object  # drive.Requests
    batches: list  # drive.Batch of every batch dispatched from t0 on
    arities: np.ndarray  # per query of the stream
    want: Optional[np.ndarray]  # the reference's count per query of the stream
    trace: Optional[dict]  # trace_reduce.reduce(...) with --trace 1
    peaks: dict  # the device's row of peaks.json
    chips: int = 1  # the chips the cell runs on

    def window_batches(self):
        return [b for b in self.batches if self.t0 <= b.t_start < self.t1]


# -- one run -----------------------------------------------------------------


def _seed(seed: int, stream: int):
    return [int(seed) % SEED_MOD, stream]


def device_info(jax, rehearse: bool, chips: int) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if not rehearse and d0.platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {d0.platform} devices")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def peak_memory(devs) -> Optional[int]:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _control_counts(ref, cq, _counts) -> np.ndarray:
    return np.array([ref.count(cq.terms(i), CONTROL_BITS) for i in range(cq.n_queries)], np.int64)


@dataclasses.dataclass
class Deployment:
    """What every run of a cell shares: the device, the configuration's
    corpus and query pool, and the program's service over the fitted
    index (sharded over the cell's chips where it asks for more than
    one).  Built once from the configuration's own seed."""

    cell: Cell
    jax: object
    device: dict
    peaks: dict
    devs: list
    corpus: object
    pool: object  # data.QueryLog
    cost: np.ndarray  # per pool query: its shortest posting list
    svc: object  # SearchService; None once freed
    traces: list  # one counter: jaxpr traces so far in this process
    want_pool: np.ndarray  # the reference's count per pool query, -1 until read


def build(cell: Cell, rehearse: bool = False) -> Deployment:
    """JAX, the corpus, the fit and the service of ``cell``: the part of
    set-up that does not depend on the run's seed."""
    if not (ROOT / "src" / "repro").is_dir():
        raise NoDevice(f"no program: {ROOT / 'src' / 'repro'} is missing")
    sys.path.insert(0, str(ROOT / "src"))
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    from jax import monitoring

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    peaks_table = load_json(BENCH / "peaks.json")
    device = device_info(jax, rehearse, cell.chips)
    if device["kind"] not in peaks_table and not rehearse:
        raise NoDevice(f"device kind {device['kind']!r} is not in bench/peaks.json")
    peaks = peaks_table.get(device["kind"], {})
    devs = jax.devices()[: cell.chips]

    from bench import data
    from repro.core.seclud import SecludPipeline
    from repro.serve.search_service import SearchService

    traces = [0]

    def on_duration(name, *_a, **_k):
        if name == "/jax/core/compile/jaxpr_trace_duration":
            traces[0] += 1

    monitoring.register_event_duration_secs_listener(on_duration)

    cfg, tr = cell.config, cell.traffic
    mix = tr["queries"]
    t_stage = time.perf_counter()
    # The deployment's data and index come from the configuration's own
    # seed, as a database benchmark's tables do; the run's seed draws the
    # query stream.
    data_seed = int(cfg["data_seed"])
    corpus = data.synth_corpus(data.CorpusSpec(**cfg["corpus"], n_docs=int(cfg["n_docs"]),
                                               n_terms=int(cfg["n_terms"]), seed=data_seed % 2**32))
    log(f"setup: corpus documents={corpus.n_docs} postings={corpus.nnz} "
        f"terms_per_doc={corpus.nnz / corpus.n_docs:.3f} in {time.perf_counter() - t_stage:.3f} s")

    def queries(n, s):
        return data.synth_query_log(corpus, n, mix["zipf_s"], mix["co_topic"],
                                    mix["frequency_weight"], s, mix["arity"], mix["arity_weights"])

    ix = cfg["index"]
    t_stage = time.perf_counter()
    train = queries(int(ix["train_queries"]), _seed(data_seed, 1))
    res = SecludPipeline(tc=int(ix["tc"]), seed=data_seed % 2**32).fit(
        corpus, k=int(ix["k"]), algo=ix["algo"], levels=int(ix["levels"]), log=train)
    svc = SearchService(res)
    log(f"setup: fit clusters={res.k} post_docs_bytes={int(svc.device_index.post_docs.nbytes)} "
        f"in {time.perf_counter() - t_stage:.3f} s")
    if cell.chips > 1:
        # The program's own sharded path: every later batch goes through
        # serve_counts_device -> sharded_device_counts over the cell's chips.
        t_stage = time.perf_counter()
        sidx = svc.enable_sharded(n_shards=cell.chips)
        log(f"setup: sharded n_shards={sidx.n_shards} "
            f"shard_postings={[int(c) for c in sidx.shard_counts]} "
            f"top_bounds={[int(b) for b in sidx.top_bounds]} in {time.perf_counter() - t_stage:.3f} s")

    # The pool: one set of queries from the configuration's seed, the
    # same work for every run; its cost (the shortest posting list of the
    # query, a bound on the cells the fold visits) sets the strata the
    # run's seed orders it by.
    pool = queries(int(tr["pool_queries"]), _seed(data_seed, 2))
    df = corpus.term_doc_freq()
    cost = np.where(pool.queries >= 0, df[np.maximum(pool.queries, 0)], np.iinfo(np.int64).max)
    return Deployment(cell=cell, jax=jax, device=device, peaks=peaks, devs=devs, corpus=corpus,
                      pool=pool, cost=cost.min(axis=1), svc=svc, traces=traces,
                      want_pool=np.full(pool.n_queries, -1, np.int64))


def _prewarm_served(svc, stream: np.ndarray, windows, chips: int) -> int:
    """Serve each window of the stream once through the program's own
    entry, for a path that has no compile-only prewarm (the sharded
    fold): every shape the window will use compiles here.  Fails at once
    where the program does not serve over the cell's chips.  Returns the
    compiles it caused."""
    compiles = 0
    for i, j in windows:
        _counts, info = svc.serve_counts_device(stream[i:j])
        if int(info.get("n_shards", 1)) != chips:
            raise RuntimeError(f"the cell asks for {chips} chips; the program served a batch "
                               f"on {info.get('n_shards', 1)} shard(s)")
        compiles += int(info.get("jit_compiles", 0))
    return compiles


def run_seed(dep: Deployment, seed: int, seconds: float, trace: bool,
             fault: Optional[Callable] = None, grace_s: float = 60.0,
             control: bool = False, free: bool = False) -> dict:
    """One run of ``dep``'s cell from its seed on: order the pool into a
    stream, prewarm, warm up, measure for ``seconds``, check every
    answer.  Returns the result object (the last line).  ``free`` drops
    the program's state before the reference runs (the last run of a
    process)."""
    from bench import data, drive, reference, trace_reduce
    from repro.core.device_engine import fold_cache_size, prewarm
    from repro.serve.loop import AsyncServingLoop, ServeConfig

    jax, cell, svc, traces = dep.jax, dep.cell, dep.svc, dep.traces
    cfg, tr = cell.config, cell.traffic

    # The stream: the pool served in passes whose order comes from the
    # run's seed, each batch a spread of the pool's cost strata.  A closed
    # loop seals consecutive runs of the stream and cycles it, so its
    # batches are the ones prewarmed.
    batch = int(cfg["serve"]["max_batch"])
    n_pool, n_stream = dep.pool.n_queries, int(tr["stream_queries"])
    if n_stream % n_pool:
        raise ValueError("stream_queries must be a multiple of pool_queries")
    order = data.serve_order(dep.cost, batch, n_stream // n_pool, _seed(seed, 3))
    stream = dep.pool.queries[order]
    terms = data.QueryLog(stream).term_lists()
    arities = data.QueryLog(stream).arities()

    t_stage = time.perf_counter()
    windows = [(i, i + batch) for i in range(0, n_stream, batch)]
    if cell.chips > 1:
        n = _prewarm_served(svc, stream, windows, cell.chips)
        log(f"setup: prewarm served batches={len(windows)} compiles={n} "
            f"in {time.perf_counter() - t_stage:.3f} s")
    else:
        pw = prewarm(svc.query_index, stream, batches=windows, dindex=svc.device_index)
        log(f"setup: prewarm keys={pw['n_keys']} compiles={pw['n_compiles']} "
            f"in {time.perf_counter() - t_stage:.3f} s")

    if control:
        # The reference at 16-bit document ids answers in the program's
        # place, on the same batches through the same loop.
        fault = functools.partial(_control_counts,
                                  reference.TermLists(dep.corpus, [t for q in terms for t in q]))

    engine = drive.Engine(svc.serve_counts_device, jax.profiler.TraceAnnotation, fault)
    loop = AsyncServingLoop(
        svc, ServeConfig(max_batch=batch, deadline_s=float(cfg["serve"]["deadline_s"])),
        engine=engine)
    trace_dir = OUT / "trace"
    state = {}

    async def session():
        await loop.start()
        t_w = time.perf_counter()
        warm = float(tr["warmup_s"])
        await drive.drive(loop, tr, terms, t_w, warm, batch=batch, grace_s=grace_s)
        state["setup_s"] = time.perf_counter() - T_PROCESS
        state["fold_before"], state["traces_before"] = fold_cache_size(), traces[0]
        n_batches = len(engine.batches)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            reqs = await drive.drive(loop, tr, terms, t0, seconds, batch=batch,
                                     grace_s=grace_s)
        if trace:
            jax.profiler.stop_trace()
        state.update(t0=t0, t1=t0 + seconds, reqs=reqs)
        state["fold_compiles"] = fold_cache_size() - state["fold_before"]
        state["traces"] = traces[0] - state["traces_before"]
        state["batches"] = engine.batches[n_batches:]
        if cell.chips > 1:  # the sharded fold's compiles, which its jit caches count
            state["fold_compiles"] += int(sum(b.info.get("jit_compiles", 0)
                                              for b in state["batches"]))
        try:
            await asyncio.wait_for(loop.stop(), timeout=grace_s)
        except Exception as e:  # the loop died or hung: its answers never came
            state["loop_error"] = f"{type(e).__name__}: {e}"

    asyncio.run(session())
    reqs = state["reqs"]
    mem = peak_memory(dep.devs)

    # Earlier lines: what the window did besides its metrics.
    sizes, freq = np.unique([b.size for b in state["batches"]], return_counts=True)
    log(f"window: requests={len(reqs.qid)} batches={len(state['batches'])} "
        f"compiles_in_window fold={state['fold_compiles']} traces={state['traces']} (expected 0)")
    log("window: batch sizes " + json.dumps({int(s): int(c) for s, c in zip(sizes, freq, strict=True)}))
    if cell.chips > 1:
        shards = sorted({int(b.info.get("n_shards", 1)) for b in state["batches"]})
        cells = np.array([b.info["shard_cells"] for b in state["batches"]
                          if len(b.info.get("shard_cells", ())) == cell.chips]).reshape(-1, cell.chips)
        log(f"window: sharded n_shards={shards} batches_with_an_idle_shard="
            f"{int((cells == 0).any(axis=1).sum())} shard_cells_mean="
            f"{[round(float(c), 1) for c in cells.mean(axis=0)] if len(cells) else []}")
        if set(shards) - {cell.chips}:
            raise RuntimeError(f"the cell asks for {cell.chips} chips; the window's batches were "
                               f"served on {shards} shard(s)")
    service = np.array([b.t_end - b.t_start for b in state["batches"]])
    if len(service):
        log(f"window: batch service s mean={service.mean():.6f} sd={service.std():.6f} "
            f"n={len(service)}")
    log(f"window: device peak_bytes_in_use={mem}")
    if "loop_error" in state:
        log(f"window: serving loop failed: {state['loop_error']}")
    tr_red = trace_reduce.reduce(trace_reduce.load(str(trace_dir))) if trace else None

    # Free the program's state, then run the reference on every answer.
    del loop, engine, svc
    if free:
        dep.svc = None
    t_stage = time.perf_counter()
    answered = ~np.isnan(reqs.reply)
    asked = np.unique(reqs.qid)
    want = np.full(n_stream, -1, np.int64)
    in_pool = np.unique(order[asked])
    todo = in_pool[dep.want_pool[in_pool] < 0]
    if len(todo):
        dep.want_pool[todo] = reference.reference_counts(
            dep.corpus, data.QueryLog(dep.pool.queries[todo]).term_lists())
    want[asked] = dep.want_pool[order[asked]]
    wrong = int((reqs.count[answered] != want[reqs.qid[answered]]).sum())
    unanswered = int((~answered).sum())
    log(f"check: {int(answered.sum())} answers against the reference in "
        f"{time.perf_counter() - t_stage:.3f} s")

    rec = Record(
        setup_s=state["setup_s"], t0=state["t0"], t1=state["t1"], requests=reqs,
        batches=state["batches"], arities=arities, want=want, trace=tr_red, peaks=dep.peaks,
        chips=cell.chips)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_metric(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    checks = {"wrong": {"value": wrong, "limit": 0},
              "unanswered": {"value": unanswered, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    dev = dict(dep.device, memory_peak_bytes=mem)
    out = {"correct": correct, "attempted": int(len(reqs.qid)), "failed": wrong + unanswered,
           "metrics": metrics, "device": dev}
    if trace:
        dev.update(busy_s=tr_red["busy_s"], window_s=tr_red["window_s"])
        out["breakdown"] = {"device_ops": tr_red["device_ops"], "idle_gaps": tr_red["idle_gaps"]}
    out["checks"] = checks
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, rehearse: bool = False,
             fault: Optional[Callable] = None, grace_s: float = 60.0,
             control: bool = False) -> dict:
    """One run of ``cell``.  Returns the result object (the last line).

    ``fault(cq, counts)`` rewrites each batch's counts on their way back
    (self-tests); ``control`` puts the reference at 16-bit document ids in
    the program's place, which the comparison has to fail."""
    dep = build(cell, rehearse)
    return run_seed(dep, seed, seconds, trace, fault=fault, grace_s=grace_s,
                    control=control, free=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at the configuration's rehearsal size")
    ap.add_argument("--control", action="store_true",
                    help="answer with the reference at 16-bit document ids in the program's place")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    cell = find_cell(args.workload, args.rehearse)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace), args.rehearse,
                       control=args.control)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
