"""Distributed SeCluD search service.

The paper's query algorithm as a serving system, at any hierarchy depth:

  * clusters are sharded over the mesh's data axis (the paper §1:
    "the resulting clusters are also useful ... for distributing the work
    over many machines") — with an L-level ``HierIndex`` the TOP level
    doubles as the machine-level router: ``pack(pin_top=True)`` groups
    rows by their level-0 ancestor so a top-level cluster's work lands on
    one contiguous run of rows, i.e. (modulo the shard boundary cut) one
    mesh shard;
  * the cluster index (term → clusters) is replicated — the paper §3.2
    argues this replication is affordable, we adopt it;
  * a query batch is broadcast, every shard intersects the posting
    segments of its local clusters, counts are combined with one psum.

Queries are arbitrary-arity conjunctions (``repro.core.queries``): the
historical ``(n, 2)`` term-pair array, the padded ``(n, max_arity)``
form, or a ``ConjunctiveQueries``.  Two execution paths with the same
contract, both on the batched planner (``repro.core.batched_query`` — no
per-query loop), both routed through the fitted ``hier_index`` when the
result carries one (the plan already encodes the whole descent; the
two-level ``cluster_index`` is the fallback and the L = 2 case):
  * ``serve_counts``       — host path (vectorized numpy Lookup, exact
    work metric, bit-identical to looping ``HierIndex.query``);
  * ``pack`` + ``device_counts`` — device path: fixed-shape padded
    rank-r segment blocks + ``shard_map`` over cluster shards.  All-pair
    batches run the single Pallas/jnp ``intersect_count`` reduction (the
    historical layout); mixed/higher arities fold the blocks pairwise
    with a masked membership select before counting survivors.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.batched_query import batched_query, gather_padded, plan_segment_pairs
from repro.core.hier_index import as_hier
from repro.core.queries import as_queries
from repro.core.seclud import SecludResult
from repro.dist import sharding as sh
from repro.kernels.intersect.ref import PAD

__all__ = ["SearchService", "PackedClusters"]


@dataclasses.dataclass
class PackedClusters:
    """Device-resident layout: for each (query, leaf-cluster-of-query)
    group the cost-ordered posting segments, padded to fixed per-rank
    widths and stacked.  ``segments[r]`` is the (R, L_r) rank-r block;
    rows whose query has fewer than r + 1 terms are all-PAD.
    ``row_top`` is each row's top-level (level-0) ancestor cluster — the
    shard-routing key (equal to the leaf cluster at L = 2, 0 at L = 1)."""

    segments: Tuple[np.ndarray, ...]
    row_query: np.ndarray  # (R,) query id of each row
    row_arity: np.ndarray  # (R,) int32 — segments actually present per row
    n_queries: int
    row_top: Optional[np.ndarray] = None  # (R,) int32 — level-0 ancestor

    @property
    def short(self) -> np.ndarray:
        """Rank-0 block (the probing side of every row's chain)."""
        return self.segments[0]

    @property
    def long(self) -> np.ndarray:
        """Rank-1 block — THE long side for the historical 2-term pack."""
        return self.segments[1]


class SearchService:
    def __init__(self, result: SecludResult):
        self.res = result
        self._device_index = None
        self._sharded = None  # ShardedDeviceIndex once enable_sharded ran
        self._elastic = None  # ElasticMesh owning the serving device pool
        self._monitor = None  # StragglerMonitor over the shards
        self._faults = None  # FaultInjector threaded into the engines

    @property
    def query_index(self):
        """The index queries route through: the fitted L-level
        ``hier_index`` when the result carries one, else the two-level
        ``cluster_index`` (stub results in tests, old pickles)."""
        hier = getattr(self.res, "hier_index", None)
        return hier if hier is not None else self.res.cluster_index

    @property
    def device_index(self):
        """The upload-once :class:`repro.core.device_engine.DeviceIndex`
        serving this service's device paths.  Built on first access (or
        inherited from ``SecludPipeline.fit``, which caches it on the
        fitted index) and reused by every subsequent batch — the index
        arrays never travel host -> device again."""
        if self._device_index is None:
            from repro.core.device_engine import device_index

            self._device_index = device_index(self.query_index)
        return self._device_index

    # -- host path -------------------------------------------------------

    def serve_counts(self, queries) -> Tuple[np.ndarray, dict]:
        """Exact per-query result counts via the hierarchical descent.

        One vectorized engine pass (``repro.core.batched_query``) — counts
        and total work are bit-identical to looping
        ``query_index.query`` over the conjunctions, at any depth.
        """
        ptr, _docs, work = batched_query(self.query_index, queries)
        return np.diff(ptr).astype(np.int64), {"work": work["total"]}

    # -- device path ------------------------------------------------------

    def serve_counts_device(self, queries, return_docs: bool = False):
        """Exact per-query counts through the device-resident engine.

        The whole cost-ordered k-way chain runs as one fused jit call
        against the persistent :attr:`device_index`; only the counts
        (and, on request, the member doc ids) return to host.  Counts
        are bit-identical to :meth:`serve_counts`; ``info`` carries the
        engine's ``n_kernel_calls`` / ``padding_overhead`` attribution
        instead of the host path's work metric.

        After :meth:`enable_sharded` the same call serves through the
        mesh-sharded engine — one ``shard_map`` dispatch over the
        per-shard corpus partitions, counts psum-combined — with results
        still bit-identical (``info`` gains the sharding attribution).
        """
        from repro.core.device_engine import device_counts, sharded_device_counts

        if self._sharded is not None:
            out = sharded_device_counts(
                self.query_index,
                queries,
                sidx=self._sharded,
                return_docs=return_docs,
                fault_hook=self._faults,
            )
            # Failover is fed from the serving path itself: every sharded
            # dispatch reports its per-shard times to the straggler
            # monitor, so a persistently slow shard is evicted and the
            # corpus re-partitioned with no manual record_shard_times
            # call.  Empty-plan batches (no device work, all-zero times)
            # are skipped — a dead batch says nothing about shard health
            # and must not reset a straggler's consecutive strikes.
            info = out[-1]
            times = info.get("shard_times")
            if (
                self._monitor is not None
                and times is not None
                and info.get("n_kernel_calls", 0.0)
                and len(times) == self._monitor.n_hosts
            ):
                _verdicts, remeshed = self.record_shard_times(times)
                info["remeshed"] = remeshed
            return out
        return device_counts(
            self.query_index,
            queries,
            dindex=self.device_index,
            return_docs=return_docs,
            fault_hook=self._faults,
        )

    # -- async serving loop -----------------------------------------------

    def serve_async(self, config=None, **config_kwargs):
        """An :class:`repro.serve.loop.AsyncServingLoop` over this
        service's device path: arrivals accumulate under a
        deadline/max-batch policy and each sealed batch dispatches as
        one fused engine call (through the mesh-sharded fold after
        :meth:`enable_sharded`).

        Pass a :class:`repro.serve.loop.ServeConfig` or its fields as
        keywords (``max_batch=``, ``deadline_s=``).  ``await start()``
        inside a running event loop; call ``prewarm()`` first so
        steady-state serving never compiles.
        """
        from repro.serve.loop import AsyncServingLoop, ServeConfig

        return AsyncServingLoop(
            self, config or ServeConfig(**config_kwargs)
        )

    # -- fault injection (chaos harness) -----------------------------------

    def install_faults(self, injector):
        """Thread a :class:`repro.serve.faults.FaultInjector` into this
        service's device dispatch paths (``None`` uninstalls).  Scheduled
        faults then fire inside ``device_counts`` /
        ``sharded_device_counts`` — the real dispatch path, not a test
        shim.  Returns the injector for chaining."""
        self._faults = injector
        return injector

    # -- sharded serving + failover ---------------------------------------

    @property
    def sharded_index(self):
        """The active :class:`repro.core.device_engine.ShardedDeviceIndex`
        (None until :meth:`enable_sharded`)."""
        return self._sharded

    @property
    def n_shards(self) -> int:
        return self._sharded.n_shards if self._sharded is not None else 0

    def enable_sharded(
        self,
        n_shards: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        deadline_factor: float = 1.5,
        strikes_to_evict: int = 3,
    ):
        """Partition the corpus over ``n_shards`` devices (or an explicit
        mesh) and route :meth:`serve_counts_device` through the sharded
        engine.

        The device pool is owned by an ``ElasticMesh`` and each shard is
        watched by a ``StragglerMonitor`` (one "host" per shard): feed
        per-step shard times to :meth:`record_shard_times` and an evicted
        shard's device is dropped from the pool, the mesh rebuilt one
        shard smaller, and the corpus re-partitioned — the lost shard's
        top-level clusters are absorbed by the survivors, results stay
        bit-identical.
        """
        from repro.core.device_engine import shard_mesh, sharded_device_index
        from repro.dist.fault_tolerance import ElasticMesh, StragglerMonitor

        if mesh is None:
            mesh = shard_mesh(n_shards)
        self._elastic = ElasticMesh(model_parallel=1)
        self._elastic.remesh(list(np.asarray(mesh.devices).reshape(-1)))
        self._sharded = sharded_device_index(
            self.query_index, mesh=self._elastic.mesh
        )
        self._monitor = StragglerMonitor(
            self._sharded.n_shards,
            deadline_factor=deadline_factor,
            strikes_to_evict=strikes_to_evict,
        )
        return self._sharded

    def record_shard_times(self, step_times):
        """Report one serving step's per-shard wall-clock times.

        Returns ``(verdicts, remeshed)``.  When the monitor's consecutive
        strikes evict a shard, its device is excluded from the elastic
        pool, the mesh rebuilt from the survivors, the corpus
        re-partitioned over the smaller mesh (top clusters of the lost
        shard re-routed to its neighbors) and a fresh monitor started for
        the new shard count.
        """
        if self._monitor is None:
            raise RuntimeError("sharded serving not enabled")
        from repro.core.device_engine import sharded_device_index
        from repro.dist.fault_tolerance import StragglerMonitor

        verdicts = self._monitor.record(step_times)
        evictees = [v.host for v in verdicts if v.evict]
        if not evictees:
            return verdicts, False
        devs = np.asarray(self._sharded.mesh.devices).reshape(
            self._sharded.n_shards, -1
        )
        for h in evictees:
            for d in devs[h]:
                self._elastic.exclude_device(int(d.id))
        mesh = self._elastic.remesh()
        self._sharded = sharded_device_index(self.query_index, mesh=mesh)
        self._monitor = StragglerMonitor(
            self._sharded.n_shards,
            deadline_factor=self._monitor.deadline_factor,
            strikes_to_evict=self._monitor.strikes_to_evict,
        )
        return verdicts, True

    def pack(self, queries, pad_to: int = 128, pin_top: bool = False) -> PackedClusters:
        """Build the fixed-shape per-(query, leaf-cluster) segment batch.

        Rows come from the batched planner (one CSR descent for the whole
        batch, no per-query loop); each query contributes one row per
        common leaf cluster holding its ``arity`` cost-ordered segments.
        An empty plan yields an honestly-empty ``(0, pad_to)`` pack —
        never a fabricated PAD row attributed to query 0.

        ``pin_top=True`` orders rows by their top-level (level-0)
        ancestor, so the contiguous row-sharding of ``device_counts``
        pins each level-0 cluster's work to one mesh shard (up to the
        single row-count cut per shard boundary).  Counts are unaffected
        — the per-query segment-sum is order-invariant.
        """
        cq = as_queries(queries)
        qidx = self.query_index
        hidx = as_hier(qidx)
        plan = plan_segment_pairs(hidx, cq)
        docs = hidx.index.post_docs
        n_rows = plan.n_pairs
        if hidx.levels:
            top_ranges = hidx.levels[0].ranges
            row_top = (
                np.searchsorted(top_ranges, plan.base, side="right") - 1
            ).astype(np.int32)
        else:
            row_top = np.zeros(n_rows, np.int32)
        sel = (
            np.argsort(row_top, kind="stable")
            if pin_top
            else np.arange(n_rows)
        )
        max_a = max(plan.max_arity, 2)  # always expose short+long blocks
        segments = []
        for r in range(max_a):
            has = plan.arity[sel] > r
            si = np.where(has, plan.seg_ptr[:-1][sel] + r, 0)  # 0 = safe index
            starts = plan.seg_start[si]
            lens = np.where(has, plan.seg_len[si], 0)
            width = max(int(lens.max()) if n_rows else 0, pad_to)
            width = -(-width // pad_to) * pad_to
            segments.append(gather_padded(docs, starts, lens, width))
        return PackedClusters(
            segments=tuple(segments),
            row_query=plan.pair_query[sel].astype(np.int32),
            row_arity=plan.arity[sel].astype(np.int32),
            n_queries=cq.n_queries,
            row_top=row_top[sel],
        )

    @staticmethod
    def device_counts(packed: PackedClusters, mesh: Optional[Mesh] = None):
        """Intersect all rows on device; segment-sum counts per query.
        With a mesh, rows are sharded over the data axis and results
        combined with one psum_scatter-equivalent reduction."""
        from repro.kernels.intersect.ops import intersect_count, intersect_members

        nq = packed.n_queries
        if packed.short.shape[0] == 0:
            return jnp.zeros(nq, jnp.int32)
        segs = tuple(jnp.asarray(b) for b in packed.segments)
        rq = jnp.asarray(packed.row_query)
        ra = jnp.asarray(packed.row_arity)
        pairs_only = bool((packed.row_arity == 2).all()) and len(segs) == 2

        def local(segs, rq, ra):
            if pairs_only:
                # The historical 2-term layout: one kernel reduction.
                c = intersect_count(segs[0], segs[1])
            else:
                # Masked pairwise fold: rows keep their running
                # intersection in the rank-0 block; rank r filters it for
                # rows with arity > r, then survivors are counted.  The
                # select runs through the members probe (Pallas kernel on
                # TPU, jnp searchsorted elsewhere).
                cur = segs[0]
                for r in range(1, len(segs)):
                    masked = intersect_members(cur, segs[r], reduce="mask")
                    active = (ra > r)[:, None]
                    cur = jnp.where(active, masked, cur)
                c = (cur != PAD).sum(axis=1).astype(jnp.int32)
            return jax.ops.segment_sum(c, rq, num_segments=nq)

        if mesh is None:
            return local(segs, rq, ra)
        # Row sharding over ALL data axes (pod included on multi-pod
        # meshes) comes from the distribution substrate, so serving and
        # training agree on what "data-parallel" means.
        dp_axes = sh.batch_axes(mesh)
        dp = sh.data_spec(mesh)
        pad = sh.shard_rows(segs[0].shape[0], mesh)
        if pad:
            segs = tuple(
                jnp.pad(s, ((0, pad), (0, 0)), constant_values=PAD) for s in segs
            )
            # Padding rows carry query id nq (out of range): segment_sum
            # drops them by construction instead of crediting query 0.
            rq = jnp.pad(rq, (0, pad), constant_values=nq)
            ra = jnp.pad(ra, (0, pad), constant_values=0)
        fn = jax.shard_map(
            lambda s, r, a: jax.lax.psum(local(s, r, a), dp_axes),
            mesh=mesh,
            in_specs=(tuple(P(dp, None) for _ in segs), P(dp), P(dp)),
            out_specs=P(),
            check_vma=True,
        )
        return fn(segs, rq, ra)
