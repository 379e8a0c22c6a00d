"""Device-resident batched query engine — upload the index once, run the
whole cost-ordered k-way chain on device, return only final counts/docs.

The previous device path (``batched_counts`` before this module) gave the
paper's work savings back as execution overhead: every chain stage
re-gathered its posting segments on the host, re-padded them into
pow2-length buckets, dispatched one kernel per bucket, pulled the hit
masks back and re-compacted the survivors in numpy — a host⇄device
ping-pong per (stage, bucket) whose wall-clock lost to the plain host
engine at arity >= 3.  This module replaces all of it with three pieces:

* :class:`DeviceIndex` — ``post_docs`` plus every :class:`HierLevel` CSR
  of a :class:`repro.core.hier_index.HierIndex`, ``jax.device_put`` once
  and cached on the host index object (so ``SecludPipeline.fit`` /
  ``SearchService`` construct it a single time and every batch reuses the
  resident arrays).

* ``lower_plan`` — lowers a host :class:`SegmentPlan` to the device *cell
  layout*: every group's rank-0 (cheapest) segment becomes a run of cells
  in one flat vector, groups ordered by arity (descending, stable).  The
  long sides are never materialized at all — each stage probes its
  posting segments *in place* inside the resident ``post_docs`` — so the
  only padding anywhere is the flat vector's tail quantization
  (``pad-to-bin-max`` degenerates to pad-to-tail here; the pow2-per-pair
  scheme and its 1.5–1.9x overhead are gone).  Every shape entering the
  jit — cell count, per-stage group width, query count — is rounded up
  at ~1/8 granularity, and each stage's search depth is the number of
  128-ary levels that covers its longest segment, so batches of similar
  size share one compiled executable instead of retracing per batch.

* ``_fused_fold`` — ONE ``jax.jit`` call executes every chain stage:
  stage s searches the surviving cells of the still-active groups
  (``arity > s``, a per-cell mask) in their group's rank-s segment
  (``lo/hi`` bounds per cell) by a 128-ary descent over fences of the
  resident postings: each level reads whole 128-lane rows and counts the
  entries at or below the cell's value, so a cell costs a few row reads
  instead of a chain of dependent scalar gathers; misses are masked to
  PAD in place — intermediate survivor lists never leave device memory.
  A final ``segment_sum`` maps cells to per-query counts.  Only the
  counts (and, on request, the member doc ids) return to host.

Exactness: counts (and docs) are bit-identical to looping
``HierIndex.query`` / ``ClusterIndex.query`` at every depth and arity —
the plan already encodes the descent, and a search masked to each
cell's own segment is exact set intersection.  On CPU the same fused
fold runs through XLA (the jnp path IS the fallback); no TPU is
required.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.runtime import maybe_validate, span
from repro.core.batched_query import _ragged_gather, _ragged_indices
from repro.core.hier_index import HierIndex, as_hier, shard_tops
from repro.core.queries import as_queries
from repro.kernels.intersect.ref import PAD

__all__ = [
    "DeviceIndex",
    "DeviceLevel",
    "device_index",
    "lower_plan",
    "device_counts",
    "fold_cache_size",
    "plan_shape_key",
    "warm_fold",
    "prewarm",
    "ShardedDeviceIndex",
    "ShardedLoweredPlan",
    "sharded_device_index",
    "lower_plan_sharded",
    "sharded_device_counts",
    "shard_mesh",
]

_CELL_ALIGN = 8  # flat cell vector tail alignment (the only padding left)
_LANES = 128  # one row the segment search reads: 128 int32 lanes, 512 bytes
# Resident postings and fences pad to whole (8, 128) int32 tiles, so the
# fold's (rows, 128) view of them is a bitcast, not a copy.
_TILE = 8 * _LANES
# Cells the segment search carries per loop step: bounds the rows one
# step gathers (a (block, 256) int32 slab at the top level, 8 MB).
_SEARCH_BLOCK = 8192


def _quantize(n: int) -> int:
    """Round ``n`` up at ~1/8 granularity (min 8).  Shapes entering the
    fused fold are quantized with this so nearby batch sizes map to the
    SAME jit cache entry — the waste is bounded by 12.5% and counted in
    ``padding_overhead``; without it every batch would retrace."""
    g = max(_CELL_ALIGN, 1 << max(int(max(n, 1) - 1).bit_length() - 3, 0))
    return -(-max(n, 1) // g) * g


def _search_levels(n: int) -> int:
    """The 128-ary levels that cover a segment of ``n`` postings: the
    least ``L >= 1`` with ``n <= 128**L``.  Level 0 is the postings' own
    rows; level j >= 1 is fence j."""
    levels = 1
    while _LANES**levels < n:
        levels += 1
    return levels


def _pad_tiles(a: np.ndarray) -> np.ndarray:
    """``a`` as int32, padded with PAD to whole (8, 128) tiles."""
    out = np.full(-(-max(len(a), 1) // _TILE) * _TILE, PAD, np.int32)
    out[: len(a)] = a
    return out


def _fences(post_docs: np.ndarray, levels: int) -> Tuple[np.ndarray, ...]:
    """Fences 1 .. ``levels - 1`` of the tile-padded ``post_docs``:
    ``fence_j[i] = post_docs[i * 128**j]``, each padded to whole tiles."""
    return tuple(_pad_tiles(post_docs[:: _LANES**j]) for j in range(1, levels))


def _check_fences(post_docs: np.ndarray, fences, what: str) -> None:
    """Every fence entry equals ``post_docs`` at its aligned position
    (PAD past the end) — the invariant the segment search's exactness
    rests on."""
    if len(post_docs) % _TILE:
        raise ValueError(f"{what}: postings not padded to whole (8, 128) tiles")
    for j, fence in enumerate(fences, start=1):
        fence = np.asarray(fence)
        pos = np.arange(len(fence), dtype=np.int64) * _LANES**j
        inside = pos < len(post_docs)
        if (
            len(fence) % _TILE
            or (fence[inside] != post_docs[pos[inside]]).any()
            or (fence[~inside] != PAD).any()
        ):
            raise ValueError(
                f"{what}: fence {j} disagrees with the postings at its "
                "aligned positions — the segment search would miss matches"
            )


# ----------------------------------------------------------------------
# The upload-once index
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DeviceLevel:
    """One :class:`repro.core.hier_index.HierLevel` CSR, device-resident."""

    cl_ptr: object  # jax.Array (n_terms + 1,) int64
    cl_ids: object  # jax.Array (nnz_l,) int32
    seg_start: object  # jax.Array (nnz_l,) int64
    seg_end: object  # jax.Array (nnz_l,) int64
    ranges: object  # jax.Array (k_l + 1,) int64


@dataclasses.dataclass(frozen=True)
class DeviceIndex:
    """The whole hierarchical index resident on device, uploaded once.

    ``post_docs`` is the array every fold probes, padded with PAD to whole
    (8, 128) tiles; ``fences`` are its 128-ary fences (fence j holds every
    ``128**j``-th posting), ``search_levels - 1`` of them, so the fold's
    segment search covers the longest posting list.  The level CSRs ride
    along so any future device-side descent finds them already resident.
    ``host`` is the host-side :class:`HierIndex` the planner runs on —
    the two views share nothing at execution time (the fold touches only
    device arrays) but stay paired so callers can't mix indexes.
    """

    post_docs: object  # jax.Array (n_postings padded to tiles,) int32
    post_ptr: object  # jax.Array (n_terms + 1,) int64
    levels: Tuple[DeviceLevel, ...]
    n_docs: int
    n_postings: int
    fences: Tuple[object, ...]  # jax.Arrays: fence j at index j - 1
    search_levels: int  # static: 128-ary levels covering the longest list
    host: HierIndex

    @property
    def nbytes(self) -> int:
        """Resident bytes (post_docs + ptr + level CSRs) — what upload
        amortizes over every subsequent batch."""
        total = int(self.post_docs.nbytes) + int(self.post_ptr.nbytes)
        total += sum(int(f.nbytes) for f in self.fences)
        for lev in self.levels:
            total += sum(
                int(getattr(lev, f).nbytes)
                for f in ("cl_ptr", "cl_ids", "seg_start", "seg_end", "ranges")
            )
        return total

    def validate(self) -> None:
        """Structural invariants the fused fold's exactness rests on
        (debug head: ``REPRO_DEBUG`` via :mod:`repro.analysis.runtime`).

        * ``post_ptr`` is a monotone CSR spanning the posting array;
        * postings are strictly increasing inside every term segment —
          the segment search (:func:`_search_segments`) is only exact on
          sorted, duplicate-free segments;
        * every level CSR is monotone with in-bounds nested segments;
        * ``search_levels`` covers the longest posting list, and every
          fence entry equals the posting at its aligned position.
        """
        post_ptr = jax.device_get(self.post_ptr)
        padded = jax.device_get(self.post_docs)
        n_post = self.n_postings
        if len(padded) < n_post or (padded[n_post:] != PAD).any():
            raise ValueError("DeviceIndex: post_docs must be n_postings then PAD")
        post_docs = padded[:n_post]
        if post_ptr[0] != 0 or post_ptr[-1] != n_post:
            raise ValueError("DeviceIndex: post_ptr must span [0, n_postings]")
        if (np.diff(post_ptr) < 0).any():
            raise ValueError("DeviceIndex: post_ptr must be nondecreasing")
        if n_post and (
            (post_docs < 0) | (post_docs >= self.n_docs)
        ).any():
            raise ValueError("DeviceIndex: posting doc ids outside [0, n_docs)")
        if n_post > 1:
            seg_start = np.zeros(n_post + 1, bool)
            seg_start[post_ptr] = True
            ok = (np.diff(post_docs) > 0) | seg_start[1:n_post]
            if not ok.all():
                raise ValueError(
                    "DeviceIndex: postings must be strictly increasing "
                    "within each term segment (binary-search invariant)"
                )
        lens = np.diff(post_ptr)
        max_len = int(lens.max()) if len(lens) else 0
        if self.search_levels < _search_levels(max_len) or len(
            self.fences
        ) != self.search_levels - 1:
            raise ValueError(
                "DeviceIndex: search_levels must cover the longest posting "
                "list with one fence per level above 0 — the fold would "
                "miss matches"
            )
        _check_fences(padded, jax.device_get(self.fences), "DeviceIndex")
        for i, lev in enumerate(self.levels):
            cl_ptr = jax.device_get(lev.cl_ptr)
            cl_ids = jax.device_get(lev.cl_ids)
            seg_s = jax.device_get(lev.seg_start)
            seg_e = jax.device_get(lev.seg_end)
            ranges = jax.device_get(lev.ranges)
            nnz = len(cl_ids)
            if cl_ptr[0] != 0 or cl_ptr[-1] != nnz or (np.diff(cl_ptr) < 0).any():
                raise ValueError(f"DeviceIndex: level {i} cl_ptr not a CSR")
            if len(seg_s) != nnz or len(seg_e) != nnz:
                raise ValueError(f"DeviceIndex: level {i} segment arity mismatch")
            bound = (
                len(jax.device_get(self.levels[i + 1].cl_ids))
                if i + 1 < len(self.levels)
                else n_post
            )
            if nnz and (
                (seg_s > seg_e) | (seg_s < 0) | (seg_e > bound)
            ).any():
                raise ValueError(
                    f"DeviceIndex: level {i} segments not nested in bounds"
                )
            if (np.diff(ranges) < 0).any():
                raise ValueError(f"DeviceIndex: level {i} ranges not monotone")
            k = len(ranges) - 1
            if nnz and ((cl_ids < 0) | (cl_ids >= k)).any():
                raise ValueError(f"DeviceIndex: level {i} node ids outside [0, k)")


def device_index(cidx) -> DeviceIndex:
    """The cached :class:`DeviceIndex` of ``cidx`` (a ``HierIndex`` of any
    depth or the two-level ``ClusterIndex`` facade), uploading on first
    use only.  The cache lives on the host ``HierIndex`` object, so every
    caller sharing an index — pipeline, service, benchmarks — shares one
    device copy."""
    hidx = as_hier(cidx)
    cached = getattr(hidx, "_device_index", None)
    if cached is not None:
        return cached
    index = hidx.index
    lens = np.diff(index.post_ptr)
    levels = _search_levels(int(lens.max()) if len(lens) else 0)
    post_docs = _pad_tiles(np.asarray(index.post_docs, np.int32))
    di = DeviceIndex(
        post_docs=jax.device_put(post_docs),
        post_ptr=jax.device_put(np.asarray(index.post_ptr, np.int64)),
        levels=tuple(
            DeviceLevel(
                cl_ptr=jax.device_put(lev.cl_ptr),
                cl_ids=jax.device_put(lev.cl_ids),
                seg_start=jax.device_put(lev.seg_start),
                seg_end=jax.device_put(lev.seg_end),
                ranges=jax.device_put(lev.ranges),
            )
            for lev in hidx.levels
        ),
        n_docs=index.n_docs,
        n_postings=len(index.post_docs),
        fences=tuple(jax.device_put(f) for f in _fences(post_docs, levels)),
        search_levels=levels,
        host=hidx,
    )
    maybe_validate(di)  # REPRO_DEBUG: structural check before caching
    hidx._device_index = di  # plain attribute: HierIndex is a mutable dataclass
    return di


# ----------------------------------------------------------------------
# Plan lowering: SegmentPlan -> flat device cell layout
# ----------------------------------------------------------------------


@dataclasses.dataclass
class LoweredPlan:
    """A :class:`SegmentPlan` in the device cell layout.

    Groups are permuted arity-descending (stable), each contributing one
    cell per element of its rank-0 segment; chain stage s (1-based)
    filters the cells whose ``cell_arity > s`` (the first
    ``group_prefix[s - 1]`` groups / ``cell_prefix[s - 1]`` cells — kept
    for attribution; the fold itself masks on the arity row so every
    array shape can be quantized for jit-cache reuse).  ``stage_seg``
    holds, per stage, each group's rank-s posting segment ``(start,
    len)`` (absolute into ``post_docs``; zeros for groups without one).
    Tail cells (quantization) carry ``cell_post = PAD``, ``arity = 0``
    and ``cell_query >= n_queries`` so the fold masks them and
    ``segment_sum`` drops them.
    """

    cells: np.ndarray  # (4, N) int32 rows: post index (PAD = pad), group
    #                    id, query id (>= n_queries = pad), arity (0 =
    #                    pad) — one upload for the whole batch
    stage_seg: np.ndarray  # (2, n_stages * group_width) int32 — per
    #                        stage, every group's (start, len), zeros
    #                        where the group has no rank-s segment
    group_width: int  # quantized per-stage width of stage_seg
    cell_prefix: Tuple[int, ...]  # true active cells per stage (host info)
    group_prefix: Tuple[int, ...]  # true active groups per stage
    stage_levels: Tuple[int, ...]  # static per-stage 128-ary search depth
    order: np.ndarray  # (G,) the arity-descending group permutation
    cell_counts: np.ndarray  # (G,) cells per permuted group (= rank-0 len)
    n_queries: int
    n_queries_pad: int  # quantized segment_sum width
    n_cells_true: int

    @property
    def n_cells(self) -> int:
        return self.cells.shape[1]

    @property
    def n_stages(self) -> int:
        return len(self.stage_levels)

    def stage_len_sum(self, s: int) -> int:
        w = self.group_width
        return int(self.stage_seg[1, s * w : (s + 1) * w].sum())


def lower_plan(plan) -> LoweredPlan:
    """Lower a host :class:`repro.core.batched_query.SegmentPlan` to the
    flat cell layout (pure numpy; the small per-batch arrays this builds
    are the only per-batch upload)."""
    n_queries = plan.n_queries
    g_arity = plan.arity.astype(np.int64)
    order = np.argsort(-g_arity, kind="stable")
    r0 = plan.seg_ptr[:-1][order]
    cell_counts = plan.seg_len[r0].astype(np.int64)
    starts0 = plan.seg_start[r0]
    n_true = int(cell_counts.sum())
    n_cells = _quantize(n_true)

    cells = np.empty((4, n_cells), np.int32)
    cells[0] = PAD
    cells[1] = len(order)
    cells[2] = n_queries
    cells[3] = 0
    if n_true:
        rows, within = _ragged_indices(cell_counts)
        cells[0, :n_true] = starts0[rows] + within
        cells[1, :n_true] = rows
        cells[2, :n_true] = plan.pair_query[order][rows]
        cells[3, :n_true] = g_arity[order][rows]

    cell_cum = np.concatenate([[0], np.cumsum(cell_counts)])
    sorted_arity = g_arity[order]
    group_width = _quantize(len(order))
    cell_prefix: List[int] = []
    group_prefix: List[int] = []
    stage_levels: List[int] = []
    seg_parts: List[np.ndarray] = []
    for s in range(1, int(plan.max_arity)):
        # Groups still active at stage s are those with arity > s — a
        # prefix of the arity-descending order; the rest keep (0, 0)
        # segments and are mask-protected by the arity row.
        n_g = int(np.searchsorted(-sorted_arity, -s, side="left"))
        if n_g == 0:
            break
        si = r0[:n_g] + s
        lens = plan.seg_len[si]
        seg = np.zeros((2, group_width), np.int32)
        seg[0, :n_g] = plan.seg_start[si]
        seg[1, :n_g] = lens
        seg_parts.append(seg)
        group_prefix.append(n_g)
        cell_prefix.append(int(cell_cum[n_g]))
        # The probed segments are cluster-local slices, usually far
        # shorter than the longest posting list: size the search to THIS
        # stage's longest segment.
        stage_levels.append(_search_levels(int(lens.max())))
    stage_seg = (
        np.concatenate(seg_parts, axis=1)
        if seg_parts
        else np.zeros((2, 0), np.int32)
    )
    return LoweredPlan(
        cells=cells,
        stage_seg=stage_seg,
        group_width=group_width,
        cell_prefix=tuple(cell_prefix),
        group_prefix=tuple(group_prefix),
        stage_levels=tuple(stage_levels),
        order=order,
        cell_counts=cell_counts,
        n_queries=n_queries,
        n_queries_pad=_quantize(n_queries),
        n_cells_true=n_true,
    )


# ----------------------------------------------------------------------
# The fused fold: every chain stage in one jit
# ----------------------------------------------------------------------


def _masked_rows(table, row, lo, hi, j: int):
    """Rows ``row`` (cells × k row indices) of search level ``j`` —
    ``table`` viewed as (rows, 128): fence j, or the postings at j = 0 —
    as one (cells, k·128) slab, with the mask of the entries whose
    position (entry × ``128**j``) lies inside ``[lo, hi)``, and each
    cell's first such entry, ``ceil(lo / 128**j)``."""
    rows_of = table.reshape(-1, _LANES)
    width = row.shape[1] * _LANES
    vals = rows_of[jnp.clip(row, 0, rows_of.shape[0] - 1)].reshape(-1, width)
    shift = 7 * j  # log2(128**j)
    first = (lo + (1 << shift) - 1) >> shift
    end = (hi + (1 << shift) - 1) >> shift
    entry = row[:, :1] * _LANES + jnp.arange(width, dtype=jnp.int32)
    inside = (entry >= first[:, None]) & (entry < end[:, None])
    return vals, inside, first


def _search_block(post_docs, fences, cur, lo, hi):
    """``cur[i]`` in ``post_docs[lo[i] : hi[i]]``, for one block of cells.

    A 128-ary descent from the top fence to the postings' own rows.  At
    fence level j (stride ``S = 128**j``) a cell's candidates are the
    entries at aligned positions inside its segment, ``ceil(lo / S) <= e
    < ceil(hi / S)``; the segment is sorted (a slice of one term's list),
    so the entries at or below ``cur`` are a prefix, and their count
    ``c`` names the one stride-S block that holds the last posting at or
    below ``cur``: entry ``max(ceil(lo / S), first of the row) + c - 1``.
    The top level's segment spans at most 128 entries, which two aligned
    rows hold; every level below searches inside one block, which one
    row holds.  Level 0 tests equality on the postings themselves.
    Every comparison is masked to ``[lo, hi)``, so rows that straddle
    term boundaries (or reach the PAD tail) never match, and a cell whose
    value is absent ends on a row where nothing equals it."""
    levels = (post_docs,) + tuple(fences)
    top = len(fences)
    first = (lo + (1 << 7 * top) - 1) >> 7 * top
    row = (first >> 7)[:, None] + jnp.arange(2, dtype=jnp.int32)
    for j in range(top, 0, -1):
        vals, inside, first = _masked_rows(levels[j], row, lo, hi, j)
        below = (inside & (vals <= cur[:, None])).sum(axis=1, dtype=jnp.int32)
        row = (jnp.maximum(first, row[:, 0] * _LANES) + below - 1)[:, None]
    vals, inside, _ = _masked_rows(post_docs, row, lo, hi, 0)
    return (inside & (vals == cur[:, None])).any(axis=1)


def _search_segments(post_docs, fences, cur, lo, hi, n_live):
    """Whether each ``cur`` element lies inside its own posting segment
    ``post_docs[lo : hi]`` — searched in place in the resident array (no
    gather of the long side, no padding) by :func:`_search_block`, over
    ``len(fences) + 1`` levels.

    Cells go through the search in blocks of ``_SEARCH_BLOCK``, so one
    step's gathered rows stay a bounded slab; only the blocks that hold
    the first ``n_live`` cells run (a traced count: the cells past it are
    reported not found).  The last block is moved back to end at the
    vector's end, so it may redo cells of the block before it, with the
    same result."""
    n = cur.shape[0]
    blk = min(_SEARCH_BLOCK, n)
    n_blocks = (n_live + blk - 1) // blk

    def body(b, found):
        at = jnp.minimum(b * blk, n - blk)
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, at, blk)
        hit = _search_block(post_docs, fences, sl(cur), sl(lo), sl(hi))
        return jax.lax.dynamic_update_slice_in_dim(found, hit, at, 0)

    return jax.lax.fori_loop(0, n_blocks, body, jnp.zeros(n, bool))


def _search_reads(n_cells: int, n_live: int, levels: int) -> int:
    """Row reads one stage's search issues: the cells its blocks carry
    (:func:`_search_segments`) times the reads a cell makes — two rows at
    the top level, one at each level below."""
    blk = min(_SEARCH_BLOCK, n_cells)
    return -(-n_live // blk) * blk * (levels + 1)


def _fold_core(
    post_docs,
    fences,
    cells,
    stage_seg,
    group_width: int,
    stage_levels: Tuple[int, ...],
    n_queries_pad: int,
    return_members: bool,
):
    """The whole multi-stage fold — the traced body shared by the
    single-device jit (:func:`_fused_fold`) and the per-shard program the
    sharded path runs under ``shard_map``.  Returns ``(tally,
    members)``: ``tally`` is one int vector, the per-query counts
    (quantized width ``n_queries_pad`` — the caller slices) followed by
    the per-stage survivor totals (live active cells entering each
    stage), so a batch reads back (and the sharded path all-reduces) one
    array; ``members`` — when ``return_members`` — is the final cell
    vector (PAD holes in place).

    Stage s filters only the cells whose group is still active
    (``arity > s``); finished groups and quantization-pad cells pass
    through untouched, so every shape here is a quantized static — the
    jit cache key is (shapes, group_width, stage_levels, n_queries_pad),
    shared by all batches of similar size.  Stage s searches with the
    first ``stage_levels[s - 1] - 1`` fences, up to the last active cell.

    Named scopes give the device ops stable names in the profiler's
    trace, whatever HLO names the compiler picks: ``seclud.fold/gather``
    (the cells' first values), ``seclud.fold/stage{s}`` (stage s's
    segment search), inside it ``seclud.fold/stage{s}/bounds`` (the
    gather of every cell's segment bounds) and ``seclud.fold/count``
    (the ``segment_sum``).
    """
    n = post_docs.shape[0]
    with jax.named_scope("seclud.fold"):
        cell_post, cell_group, cell_query, cell_arity = (
            cells[0], cells[1], cells[2], cells[3],
        )
        with jax.named_scope("gather"):
            cur = post_docs[jnp.clip(cell_post, 0, n - 1)]
            cur = jnp.where(cell_post != PAD, cur, PAD)
        position = jnp.arange(1, cell_arity.shape[0] + 1, dtype=jnp.int32)
        entering = []
        for s, levels in enumerate(stage_levels, start=1):
            with jax.named_scope(f"stage{s}"):
                seg = stage_seg[:, (s - 1) * group_width : s * group_width]
                with jax.named_scope("bounds"):
                    lo = seg[0][cell_group]
                    hi = lo + seg[1][cell_group]
                act = cell_arity > s
                entering.append(((cur != PAD) & act).sum())
                n_live = jnp.max(jnp.where(act, position, 0))
                found = _search_segments(
                    post_docs, fences[: levels - 1], cur, lo, hi, n_live
                )
                cur = jnp.where(act & ~found, PAD, cur)
        with jax.named_scope("count"):
            counts = jax.ops.segment_sum(
                (cur != PAD).astype(jnp.int32), cell_query, num_segments=n_queries_pad
            )
        tally = jnp.concatenate([counts, jnp.stack(entering).astype(counts.dtype)]
                                if entering else [counts])
    return tally, (cur if return_members else None)


_fused_fold = functools.partial(
    jax.jit,
    static_argnames=(
        "group_width",
        "stage_levels",
        "n_queries_pad",
        "return_members",
    ),
)(_fold_core)


# ----------------------------------------------------------------------
# Shape-grid prewarm: compile the fold's cache entries at startup
# ----------------------------------------------------------------------
#
# The fused fold's jit-cache key is the quantized shape tuple
# (n_cells, group_width, stage_levels, n_queries_pad) — everything else
# is traced data.  A serving loop can therefore enumerate the keys its
# batch plan will produce, compile each once on *dead* cell content
# (all-PAD cells, zero segments — the fold is mask-safe by design), and
# then serve indefinitely without a single steady-state compile.


def fold_cache_size() -> int:
    """Compiled-entry count of the fused fold — the serving loop's
    compile counter."""
    from repro.analysis.sanitize import jit_cache_size

    return jit_cache_size(_fused_fold)


def plan_shape_key(lowered: LoweredPlan) -> Tuple[int, int, Tuple[int, ...], int]:
    """The jit-cache key of a lowered plan: the quantized shape tuple
    ``(n_cells, group_width, stage_levels, n_queries_pad)``.  Two plans
    with equal keys share one compiled executable."""
    return (
        lowered.n_cells,
        lowered.group_width,
        lowered.stage_levels,
        lowered.n_queries_pad,
    )


def warm_fold(
    dindex: DeviceIndex,
    key: Tuple[int, int, Tuple[int, ...], int],
    return_members: bool = False,
) -> None:
    """Compile the fused fold for one shape key without a real plan.

    Builds dead content of exactly the key's shapes — all-PAD cells with
    arity 0 and out-of-range query ids, zero-length segments — so the
    executable lands in the jit cache at startup cost but near-zero
    execution cost.  The fold masks dead cells everywhere, so warming
    content never touches real postings.
    """
    n_cells, group_width, stage_levels, n_queries_pad = key
    cells = np.empty((4, n_cells), np.int32)
    cells[0] = PAD
    cells[1] = 0
    cells[2] = n_queries_pad
    cells[3] = 0
    stage_seg = np.zeros((2, len(stage_levels) * group_width), np.int32)
    out = _fused_fold(
        dindex.post_docs,
        dindex.fences,
        jax.device_put(cells),
        jax.device_put(stage_seg),
        group_width=group_width,
        stage_levels=tuple(stage_levels),
        n_queries_pad=n_queries_pad,
        return_members=return_members,
    )
    jax.device_get(out[0])  # block: the compile is done when we return


def prewarm(
    cidx,
    queries,
    batch_sizes: Optional[Sequence[int]] = None,
    batches: Optional[Sequence[Tuple[int, int]]] = None,
    dindex: Optional[DeviceIndex] = None,
    return_members: bool = False,
) -> Dict[str, object]:
    """Pre-compile the fused fold's quantized shape grid for a workload.

    ``queries`` is a representative sample (e.g. yesterday's log);
    either ``batches`` gives explicit ``(start, end)`` windows into it —
    e.g. the exact windows :func:`repro.serve.loop.plan_batches` will
    dispatch — or ``batch_sizes`` names prefix sizes to warm.  Each
    window is planned and lowered on host only (cheap) to find its shape
    key; each distinct key compiles once via :func:`warm_fold`.

    Returns ``{"n_batches", "n_keys", "n_compiles", "keys"}`` —
    ``n_compiles <= n_keys`` since some keys may already be cached.
    """
    from repro.core.batched_query import plan_segment_pairs

    cq = as_queries(queries)
    if dindex is None:
        dindex = device_index(cidx)
    if batches is None:
        if batch_sizes is None:
            raise ValueError("prewarm needs batch_sizes or explicit batches")
        batches = [(0, min(int(b), cq.n_queries)) for b in batch_sizes]
    before = fold_cache_size()
    keys: List[Tuple[int, int, Tuple[int, ...], int]] = []
    seen = set()
    n_batches = 0
    for i, j in batches:
        if j <= i:
            continue
        n_batches += 1
        plan = plan_segment_pairs(dindex.host, cq[int(i) : int(j)], track_work=False)
        if plan.n_pairs == 0:
            continue  # empty plans never reach the fold
        key = plan_shape_key(lower_plan(plan))
        if key in seen:
            continue
        seen.add(key)
        keys.append(key)
        warm_fold(dindex, key, return_members=return_members)
    return {
        "n_batches": n_batches,
        "n_keys": len(keys),
        "n_compiles": fold_cache_size() - before,
        "keys": keys,
    }


# ----------------------------------------------------------------------
# Public entry: counts (and docs) for a whole batch
# ----------------------------------------------------------------------


def _stage_info(lowered: LoweredPlan, entering: np.ndarray) -> List[Dict[str, float]]:
    """Per-stage attribution: how many cells the stage carried (padded),
    how many were live survivors (true), how many posting cells it probed
    in place, the resulting padding overhead, and the 128-lane row reads
    its search issued (``reads``)."""
    stages = []
    for s in range(len(lowered.cell_prefix)):
        carried = float(lowered.cell_prefix[s])
        live = float(entering[s]) if s < len(entering) else carried
        long_cells = float(lowered.stage_len_sum(s))
        stages.append(
            {
                "stage": float(s + 1),
                "cur_cells": carried,
                "cur_live": live,
                "long_cells": long_cells,
                "padding_overhead": (carried + long_cells)
                / max(live + long_cells, 1.0),
                "reads": float(
                    _search_reads(
                        lowered.n_cells,
                        lowered.cell_prefix[s],
                        lowered.stage_levels[s],
                    )
                ),
            }
        )
    return stages


def _late_info(stages: List[Dict[str, float]]) -> Dict[str, float]:
    """The cells the stages after the first carry into their searches
    (``late_cells``) and, of those, the ones still live on entering the
    stage (``late_live``): the rest were ruled out by an earlier stage
    and are searched all the same."""
    return {
        "late_cells": float(sum(s["cur_cells"] for s in stages[1:])),
        "late_live": float(sum(s["cur_live"] for s in stages[1:])),
    }


# ``info`` of a batch with no (query, cluster) pair: nothing reaches the
# device.
_EMPTY_INFO = {
    "n_pairs": 0.0,
    "n_kernel_calls": 0.0,
    "padding_overhead": 1.0,
    "cells": 0.0,
    "cells_true": 0.0,
    "upload_bytes": 0.0,
    "search_reads": 0.0,
    "late_cells": 0.0,
    "late_live": 0.0,
    "t_lower_s": 0.0,
    "t_fold_s": 0.0,
    "jit_compiles": 0.0,
}


def device_counts(
    cidx,
    queries,
    plan=None,
    dindex: Optional[DeviceIndex] = None,
    return_docs: bool = False,
    fault_hook=None,
):
    """Per-query result counts of a conjunctive batch, fully on device.

    ``cidx`` is a ``HierIndex`` of any depth or the ``ClusterIndex``
    facade; the resident :class:`DeviceIndex` is looked up (or built on
    first use) unless passed explicitly.  Returns ``(counts, info)`` —
    or ``(counts, docs, info)`` with ``return_docs=True``, where ``docs``
    is the CSR value array bit-identical to ``batched_query``'s.

    ``info`` keys: ``n_pairs``, ``n_kernel_calls`` (fused dispatches for
    the whole batch — 1), ``padding_overhead`` (cells materialized /
    true cells; the long sides are probed in place and contribute zero
    padding), ``occupancy`` (live survivor cells / cells carried across
    all stages — the masked-execution analogue of pad waste), and
    ``stages`` (per-stage attribution dicts).  The cells the batch
    uploads: ``cells`` (padded cells the fold carries), ``cells_true``
    and ``upload_bytes`` (the per-batch ``device_put`` bytes).
    ``search_reads`` is the 128-lane row reads the stages' segment
    searches issued (each stage's share is its ``reads``).
    ``late_cells`` is the cells the stages after the first carry into
    their searches, ``late_live`` those of them still live on entering
    the stage (both sums over stages 2 on of the stages' ``cur_cells``
    and ``cur_live``).  Per-call
    timing hooks for the serving loop ride along: ``t_plan_s`` /
    ``t_lower_s`` / ``t_fold_s`` split the call into host planning,
    lowering, and the fused dispatch (upload, dispatch and readback: the
    device round-trip); each is the duration of the profiler span of the
    same interval (``seclud.plan``, ``seclud.lower``, then from the
    start of ``seclud.upload`` to the end of ``seclud.readback``, timed
    as one interval so that the instants between the three spans count
    as they do in the trace).
    ``jit_compiles`` is the fold-cache growth this call caused (0 on
    every warm path).
    """
    from repro.core.batched_query import plan_segment_pairs

    info: Dict[str, object] = {}
    with span("seclud.plan", info, "t_plan_s"):
        cq = as_queries(queries)
        if dindex is None:
            dindex = device_index(cidx)
        if plan is None:
            # The device path needs the segment layout, not the paper's
            # work metric — plan without the probe/scan accounting.
            plan = plan_segment_pairs(dindex.host, cq, track_work=False)
    if fault_hook is not None:
        # Injection point of the chaos harness (repro.serve.faults): a
        # scheduled fault raises here, inside the real dispatch path —
        # exactly where a device error would surface — so the resilience
        # ladder is exercised without patching the engine in tests.
        fault_hook.on_dispatch(n_shards=1)
    if plan.n_pairs == 0:
        counts = np.zeros(plan.n_queries, np.int64)
        info.update(_EMPTY_INFO, occupancy=1.0, stages=[])
        if return_docs:
            return counts, np.empty(0, np.int32), info
        return counts, info

    with span("seclud.lower", info, "t_lower_s"):
        lowered = lower_plan(plan)
    cache_before = fold_cache_size()
    with span("seclud.upload") as t_fold:
        cells_d = jax.device_put(lowered.cells)
        stage_seg_d = jax.device_put(lowered.stage_seg)
    with span("seclud.dispatch"):
        tally_d, members_d = _fused_fold(
            dindex.post_docs,
            dindex.fences,
            cells_d,
            stage_seg_d,
            group_width=lowered.group_width,
            stage_levels=lowered.stage_levels,
            n_queries_pad=lowered.n_queries_pad,
            return_members=return_docs,
        )
    with span("seclud.readback"):
        tally = jax.device_get(tally_d)
        counts = tally[: lowered.n_queries].astype(np.int64)
        info["t_fold_s"] = time.perf_counter() - t_fold
    entering = tally[lowered.n_queries_pad :]

    stages = _stage_info(lowered, entering)
    true_cells = float(lowered.n_cells_true)
    long_cells = float(sum(s["long_cells"] for s in stages))
    carried = float(lowered.n_cells) + sum(s["cur_cells"] for s in stages)
    live = true_cells + sum(s["cur_live"] for s in stages)
    info.update(
        n_pairs=float(plan.n_pairs),
        n_kernel_calls=1.0,
        padding_overhead=(float(lowered.n_cells) + long_cells)
        / max(true_cells + long_cells, 1.0),
        occupancy=live / max(carried, 1.0),
        stages=stages,
        cells=float(lowered.n_cells),
        cells_true=true_cells,
        upload_bytes=float(lowered.cells.nbytes + lowered.stage_seg.nbytes),
        search_reads=sum(s["reads"] for s in stages),
        **_late_info(stages),
        jit_compiles=float(fold_cache_size() - cache_before),
    )
    if not return_docs:
        return counts, info

    # Un-permute the final cells to plan (query, cluster) order; dropping
    # PAD holes leaves exactly batched_query's doc array.
    members = jax.device_get(members_d)
    perm_start = np.concatenate([[0], np.cumsum(lowered.cell_counts)])[:-1]
    inv_order = np.empty(len(lowered.order), np.int64)
    inv_order[lowered.order] = np.arange(len(lowered.order))
    orig_cells = _ragged_gather(
        members, perm_start[inv_order], lowered.cell_counts[inv_order]
    )
    docs = orig_cells[orig_cells != PAD].astype(np.int32)
    return counts, docs, info


# ----------------------------------------------------------------------
# Mesh-sharded serving: per-shard postings, fused fold under shard_map
# ----------------------------------------------------------------------
#
# The corpus is partitioned by level-0 ancestor into S contiguous
# doc-id ranges (``shard_tops`` balances posting mass), each shard
# holding the postings of its own docs as one row of a stacked (S, W)
# matrix laid over the mesh's data axis.  Because every segment group of
# a plan lives inside ONE leaf cluster — hence one top cluster, hence
# one shard — the global plan routes exactly: each group's cells land on
# the shard owning its docs, untouched shards receive only dead
# (masked) cells.  One ``shard_map`` call then runs :func:`_fold_core`
# per shard and a single ``psum`` over the data axes produces the final
# counts; member docs come back per-shard and are re-concatenated on
# host in original plan-group order, bit-identical to the single-device
# path.


def shard_mesh(n_shards: Optional[int] = None):
    """A ``(n_shards, 1)`` mesh over the first ``n_shards`` local devices
    with the canonical ``("data", "model")`` axes — the serving mesh the
    sharded engine partitions the corpus over (defaults to every
    device)."""
    from jax.sharding import Mesh

    devs = jax.devices()
    if n_shards is None:
        n_shards = len(devs)
    if not 1 <= n_shards <= len(devs):
        raise ValueError(
            f"n_shards={n_shards} outside [1, {len(devs)}] available devices"
        )
    return Mesh(np.asarray(devs[:n_shards]).reshape(n_shards, 1), ("data", "model"))


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedDeviceIndex:
    """The corpus partitioned by level-0 ancestor over a mesh's data axis.

    ``post_docs`` is a (S, W) matrix — row s holds shard s's postings
    (the global postings whose doc id falls in ``[doc_bounds[s],
    doc_bounds[s + 1])``, order preserved, PAD beyond ``shard_counts[s]``)
    — laid out with ``NamedSharding`` so each mesh shard holds exactly
    its own row.  ``local_pos`` maps a global posting position to its
    position within its shard's row: a plan segment (contiguous globally,
    wholly inside one leaf cluster and therefore one shard) stays
    contiguous locally, so lowering only remaps segment starts.  W is a
    whole number of (8, 128) tiles; ``fences`` stack each row's 128-ary
    fences (:class:`DeviceIndex`) the same way, one (S, F_j) matrix per
    fence level, ``search_levels - 1`` of them.
    """

    mesh: object  # jax.sharding.Mesh
    n_shards: int
    top_bounds: np.ndarray  # (S + 1,) level-0 node boundaries per shard
    doc_bounds: np.ndarray  # (S + 1,) doc-id boundaries per shard
    post_docs: object  # jax.Array (S, W) int32, sharded P(data, None)
    post_width: int  # W — quantized max shard posting count
    local_pos: np.ndarray  # (n_postings,) int64 — global -> within-shard
    shard_counts: np.ndarray  # (S,) int64 — true postings per shard
    fences: Tuple[object, ...]  # jax.Arrays (S, F_j), sharded P(data, None)
    search_levels: int  # 128-ary levels covering the longest posting list
    host: HierIndex

    @property
    def nbytes(self) -> int:
        """Total resident bytes across the mesh (PAD tail included)."""
        return int(self.post_docs.nbytes) + sum(int(f.nbytes) for f in self.fences)

    def validate(self) -> None:
        """Shard partition exactness (debug head: ``REPRO_DEBUG``).

        The sharded fold is bit-identical to the single-device path only
        if the (S, W) stacked postings are an exact partition: every
        global posting sits at ``(shard_of(doc), local_pos)`` in its
        owner's row, rows carry nothing else but PAD tail, and the
        doc-range routing that ``lower_plan_sharded`` uses reproduces
        the row assignment.  Every row's fences equal the row at their
        aligned positions, and ``search_levels`` covers the longest
        posting list.
        """
        S = self.n_shards
        if len(self.top_bounds) != S + 1 or len(self.doc_bounds) != S + 1:
            raise ValueError("ShardedDeviceIndex: bounds must have S + 1 entries")
        if (np.diff(self.top_bounds) < 0).any() or (
            np.diff(self.doc_bounds) < 0
        ).any():
            raise ValueError("ShardedDeviceIndex: shard bounds not monotone")
        docs = np.asarray(self.host.index.post_docs, np.int64)
        n_post = len(docs)
        if len(self.local_pos) != n_post:
            raise ValueError("ShardedDeviceIndex: local_pos length mismatch")
        if int(self.shard_counts.sum()) != n_post:
            raise ValueError(
                "ShardedDeviceIndex: shard_counts do not partition the postings"
            )
        stacked = jax.device_get(self.post_docs)
        if stacked.shape != (S, self.post_width):
            raise ValueError("ShardedDeviceIndex: stacked postings shape mismatch")
        shard_of = np.clip(
            np.searchsorted(self.doc_bounds, docs, side="right") - 1, 0, S - 1
        )
        if not np.array_equal(
            np.bincount(shard_of, minlength=S).astype(np.int64),
            self.shard_counts,
        ):
            raise ValueError(
                "ShardedDeviceIndex: shard_counts disagree with doc-range routing"
            )
        live = np.zeros((S, self.post_width), bool)
        if n_post:
            if ((self.local_pos < 0) | (self.local_pos >= self.post_width)).any():
                raise ValueError("ShardedDeviceIndex: local_pos outside its row")
            if not (stacked[shard_of, self.local_pos] == docs).all():
                raise ValueError(
                    "ShardedDeviceIndex: a posting is not at its routed "
                    "(shard, local) slot — partition is not exact"
                )
            live[shard_of, self.local_pos] = True
            if int(live.sum()) != n_post:
                raise ValueError(
                    "ShardedDeviceIndex: local_pos collides within a shard"
                )
        if (stacked[~live] != PAD).any():
            raise ValueError(
                "ShardedDeviceIndex: non-PAD value outside the live partition"
            )
        lens = np.diff(self.host.index.post_ptr)
        if self.search_levels < _search_levels(
            int(lens.max()) if len(lens) else 0
        ) or len(self.fences) != self.search_levels - 1:
            raise ValueError(
                "ShardedDeviceIndex: search_levels must cover the longest "
                "posting list with one fence per level above 0"
            )
        fences = jax.device_get(self.fences)
        for s in range(S):
            _check_fences(
                stacked[s], [f[s] for f in fences], f"ShardedDeviceIndex row {s}"
            )


def sharded_device_index(
    cidx, mesh=None, n_shards: Optional[int] = None
) -> ShardedDeviceIndex:
    """The cached :class:`ShardedDeviceIndex` of ``cidx`` over ``mesh``
    (built from ``n_shards`` local devices when omitted).  Cached per
    mesh on the host ``HierIndex``, so re-serving after a remesh (shard
    failover) rebuilds once and every later batch reuses the upload."""
    from repro.dist import sharding as sh
    from jax.sharding import NamedSharding

    hidx = as_hier(cidx)
    if mesh is None:
        mesh = shard_mesh(n_shards)
    cache = getattr(hidx, "_sharded_indexes", None)
    if cache is None:
        cache = {}
        hidx._sharded_indexes = cache
    cached = cache.get(mesh)
    if cached is not None:
        return cached

    S = sh.axes_size(mesh, sh.data_spec(mesh))
    top_bounds = shard_tops(hidx, S)
    doc_bounds = hidx.top_ranges[top_bounds].astype(np.int64)
    docs = np.asarray(hidx.index.post_docs, np.int64)
    n_post = len(docs)
    shard_of = np.clip(
        np.searchsorted(doc_bounds, docs, side="right") - 1, 0, S - 1
    )
    shard_counts = np.bincount(shard_of, minlength=S).astype(np.int64)
    shard_off = np.concatenate([[0], np.cumsum(shard_counts)])
    order = np.argsort(shard_of, kind="stable")
    local = np.arange(n_post, dtype=np.int64) - np.repeat(
        shard_off[:-1], shard_counts
    )
    local_pos = np.empty(n_post, np.int64)
    local_pos[order] = local
    width = _quantize(int(shard_counts.max()) if n_post else 1)
    width = -(-width // _TILE) * _TILE
    stacked = np.full((S, width), PAD, np.int32)
    stacked[shard_of, local_pos] = docs.astype(np.int32)
    lens = np.diff(hidx.index.post_ptr)
    levels = _search_levels(int(lens.max()) if len(lens) else 0)
    rows = NamedSharding(mesh, sh.postings_spec(mesh))
    sidx = ShardedDeviceIndex(
        mesh=mesh,
        n_shards=S,
        top_bounds=top_bounds,
        doc_bounds=doc_bounds,
        post_docs=jax.device_put(stacked, rows),
        post_width=width,
        local_pos=local_pos,
        shard_counts=shard_counts,
        fences=tuple(
            jax.device_put(np.stack(f), rows)
            for f in zip(*(_fences(row, levels) for row in stacked))
        ),
        search_levels=levels,
        host=hidx,
    )
    maybe_validate(sidx)  # REPRO_DEBUG: partition exactness before caching
    cache[mesh] = sidx
    return sidx


def _take_groups(plan, g_idx: np.ndarray, sidx: ShardedDeviceIndex):
    """The sub-:class:`SegmentPlan` of groups ``g_idx``, segment starts
    remapped into the owning shard's local postings row.  Query ids stay
    global — per-shard counts segment-sum into the full query range and
    the cross-shard psum adds disjoint contributions."""
    from repro.core.batched_query import SegmentPlan

    arity = plan.arity[g_idx].astype(np.int64)
    rows, within = _ragged_indices(arity)
    si = plan.seg_ptr[:-1][g_idx][rows] + within
    seg_len = plan.seg_len[si]
    gstart = plan.seg_start[si]
    n_post = len(sidx.local_pos)
    # Empty segments may sit at the postings tail (start == n_postings):
    # clamp the lookup, their remapped start is never probed.
    seg_start = np.where(
        seg_len > 0,
        sidx.local_pos[np.minimum(gstart, max(n_post - 1, 0))],
        0,
    )
    return SegmentPlan(
        pair_query=plan.pair_query[g_idx],
        cluster=plan.cluster[g_idx],
        base=plan.base[g_idx],
        width=plan.width[g_idx],
        arity=arity,
        seg_ptr=np.concatenate([[0], np.cumsum(arity)]).astype(np.int64),
        seg_start=seg_start.astype(np.int64),
        seg_len=seg_len.astype(np.int64),
        cluster_work=np.zeros(plan.n_queries, np.int64),
        n_queries=plan.n_queries,
        max_arity=int(plan.max_arity),
    )


@dataclasses.dataclass
class ShardedLoweredPlan:
    """A :class:`SegmentPlan` lowered per shard and stacked for one
    ``shard_map`` dispatch: shard s's cells/segments sit in row s (dead
    cells where another shard owns the group), shapes unified across
    shards so a single compiled program serves the whole mesh.
    ``grp_shard`` / ``grp_off`` / ``grp_cnt`` locate every original plan
    group inside the stacked member matrix — the host-side gather that
    restores single-device doc order exactly."""

    cells: np.ndarray  # (S, 4, C) int32 — per-shard cell layout
    stage_seg: np.ndarray  # (S, 2, n_stages * group_width) int32
    group_width: int  # unified quantized per-stage width
    stage_levels: Tuple[int, ...]  # per-stage max 128-ary search depth
    stage_reads: Tuple[int, ...]  # per-stage row reads, all shards
    stage_cells: Tuple[int, ...]  # per-stage true active cells, all shards
    n_queries: int
    n_queries_pad: int
    n_cells_true: np.ndarray  # (S,) true cells per shard (load balance)
    grp_shard: np.ndarray  # (G,) owning shard of each original group
    grp_off: np.ndarray  # (G,) cell offset inside the shard's row
    grp_cnt: np.ndarray  # (G,) cells of the group (= rank-0 len)
    shards_touched: int
    n_shards: int

    @property
    def n_cells(self) -> int:
        return self.cells.shape[2]

    @property
    def n_stages(self) -> int:
        return len(self.stage_levels)


def lower_plan_sharded(plan, sidx: ShardedDeviceIndex) -> ShardedLoweredPlan:
    """Route a global plan's groups to their owning shards and lower each
    shard's slice (pure numpy).  A group's top-level ancestor decides its
    shard — the level-0 descent IS the router; shards outside the batch's
    descent receive only dead cells and contribute nothing but a masked
    no-op to the fused fold."""
    S = sidx.n_shards
    top = np.searchsorted(sidx.host.top_ranges, plan.base, side="right") - 1
    gshard = np.clip(
        np.searchsorted(sidx.top_bounds, top, side="right") - 1, 0, S - 1
    ).astype(np.int64)

    lowereds = {}
    for s in np.unique(gshard):
        g_idx = np.flatnonzero(gshard == s)
        lowereds[int(s)] = (g_idx, lower_plan(_take_groups(plan, g_idx, sidx)))

    # Unify shapes across shards: one compiled executable for the mesh.
    width = max(low.group_width for _, low in lowereds.values())
    n_cells = max(low.n_cells for _, low in lowereds.values())
    n_stages = max(low.n_stages for _, low in lowereds.values())
    levels = [1] * n_stages
    for _, low in lowereds.values():
        for t, lv in enumerate(low.stage_levels):
            levels[t] = max(levels[t], lv)
    reads = [
        sum(
            _search_reads(n_cells, low.cell_prefix[t], levels[t])
            for _, low in lowereds.values()
            if t < low.n_stages
        )
        for t in range(n_stages)
    ]
    active = [
        sum(low.cell_prefix[t] for _, low in lowereds.values() if t < low.n_stages)
        for t in range(n_stages)
    ]
    n_queries = plan.n_queries

    cells = np.empty((S, 4, n_cells), np.int32)
    cells[:, 0] = PAD
    cells[:, 1] = width
    cells[:, 2] = n_queries
    cells[:, 3] = 0
    stage_seg = np.zeros((S, 2, n_stages * width), np.int32)
    n_true = np.zeros(S, np.int64)
    n_groups = plan.n_pairs
    grp_off = np.zeros(n_groups, np.int64)
    grp_cnt = np.zeros(n_groups, np.int64)
    for s, (g_idx, low) in lowereds.items():
        cells[s, :, : low.n_cells] = low.cells
        gw = low.group_width
        for t in range(low.n_stages):
            stage_seg[s, :, t * width : t * width + gw] = low.stage_seg[
                :, t * gw : (t + 1) * gw
            ]
        n_true[s] = low.n_cells_true
        perm_start = np.concatenate([[0], np.cumsum(low.cell_counts)])[:-1]
        inv = np.empty(len(low.order), np.int64)
        inv[low.order] = np.arange(len(low.order))
        grp_off[g_idx] = perm_start[inv]
        grp_cnt[g_idx] = low.cell_counts[inv]
    return ShardedLoweredPlan(
        cells=cells,
        stage_seg=stage_seg,
        group_width=width,
        stage_levels=tuple(levels),
        stage_reads=tuple(reads),
        stage_cells=tuple(active),
        n_queries=n_queries,
        n_queries_pad=_quantize(n_queries),
        n_cells_true=n_true,
        grp_shard=gshard,
        grp_off=grp_off,
        grp_cnt=grp_cnt,
        shards_touched=len(lowereds),
        n_shards=S,
    )


@functools.lru_cache(maxsize=64)
def _build_sharded_fold(
    mesh,
    group_width: int,
    stage_levels: Tuple[int, ...],
    n_queries_pad: int,
    return_members: bool,
):
    """The compiled sharded fold for one (mesh, quantized-shape) key:
    ``shard_map`` runs :func:`_fold_core` on each shard's row and a
    single ``psum`` over the data axes produces the global counts —
    cached so batches of similar size reuse one executable, exactly like
    the single-device jit cache."""
    from jax.sharding import PartitionSpec as P

    from repro.dist import sharding as sh

    dp_axes = sh.batch_axes(mesh)
    cells_spec, seg_spec = sh.plan_specs(mesh)

    def body(post_docs, fences, cells, stage_seg):
        tally, cur = _fold_core(
            post_docs[0],
            tuple(f[0] for f in fences),
            cells[0],
            stage_seg[0],
            group_width=group_width,
            stage_levels=stage_levels,
            n_queries_pad=n_queries_pad,
            return_members=return_members,
        )
        tally = jax.lax.psum(tally, dp_axes)
        if return_members:
            return tally, cur[None]
        return (tally,)

    out_specs = (P(),)
    if return_members:
        out_specs = out_specs + (sh.postings_spec(mesh),)
    # check_vma=False: the psum over the data axes is what makes the
    # counts replicated; the fold's gathers carry no varying-axis types.
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(sh.postings_spec(mesh), sh.postings_spec(mesh), cells_spec, seg_spec),
        out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_device_counts(
    cidx,
    queries,
    plan=None,
    sidx: Optional[ShardedDeviceIndex] = None,
    return_docs: bool = False,
    fault_hook=None,
):
    """Per-query result counts over the mesh-sharded corpus — one
    ``shard_map`` dispatch, counts combined with one psum.

    ``cidx`` is any host index (or a :class:`ShardedDeviceIndex`, whose
    mesh is then reused).  Counts AND member docs are bit-identical to
    :func:`device_counts` and the host loop: the plan is global, each
    group's work runs on the one shard owning its docs, and docs are
    re-gathered in original plan-group order on host.

    ``info`` adds the sharding attribution: ``n_shards``,
    ``shards_touched`` (level-0 routing), ``shard_cells`` (true cells
    per shard), ``shard_times`` (per-shard dispatch seconds — what
    ``SearchService.record_shard_times`` consumes for failover),
    ``agg_throughput`` (total true cells / max per-shard true
    cells — the deterministic load-balance speedup bound) and
    ``load_balance`` (= agg_throughput / n_shards, the scaling
    efficiency).  ``cells`` counts every shard's padded cells (``n_shards
    × n_cells``); ``cells_true``, ``upload_bytes``, ``search_reads``,
    ``late_cells``, ``late_live`` (with each stage's ``cur_cells``,
    ``cur_live`` and ``reads`` in ``stages``, summed over shards) and the
    ``t_*_s`` spans are as in :func:`device_counts`.  ``fault_hook`` is the chaos
    harness's injection point (:mod:`repro.serve.faults`): called inside
    the dispatch path, where it may raise scheduled faults and perturb
    ``shard_times``."""
    from jax.sharding import NamedSharding

    from repro.analysis.sanitize import jit_cache_size
    from repro.core.batched_query import plan_segment_pairs
    from repro.dist import sharding as sh

    info: Dict[str, object] = {}
    with span("seclud.plan", info, "t_plan_s"):
        cq = as_queries(queries)
        if sidx is None:
            sidx = (
                cidx
                if isinstance(cidx, ShardedDeviceIndex)
                else sharded_device_index(cidx)
            )
        if plan is None:
            plan = plan_segment_pairs(sidx.host, cq, track_work=False)
    if fault_hook is not None:
        # Chaos-harness injection point (repro.serve.faults): scheduled
        # faults raise here, inside the real sharded dispatch path; the
        # hook also watches n_shards to retire device-loss events once
        # failover re-partitioned without the lost shard.
        fault_hook.on_dispatch(n_shards=sidx.n_shards)
    if plan.n_pairs == 0:
        counts = np.zeros(plan.n_queries, np.int64)
        info.update(
            _EMPTY_INFO,
            n_shards=float(sidx.n_shards),
            shards_touched=0.0,
            shard_cells=[0.0] * sidx.n_shards,
            shard_times=[0.0] * sidx.n_shards,
            agg_throughput=1.0,
            load_balance=1.0 / max(sidx.n_shards, 1),
            stages=[],
        )
        if return_docs:
            return counts, np.empty(0, np.int32), info
        return counts, info

    with span("seclud.lower", info, "t_lower_s"):
        lowered = lower_plan_sharded(plan, sidx)
        fold = _build_sharded_fold(
            sidx.mesh,
            lowered.group_width,
            lowered.stage_levels,
            lowered.n_queries_pad,
            bool(return_docs),
        )
    cache_before = jit_cache_size(fold)
    # Explicit per-batch upload, pre-placed shard-per-row so the jit
    # never reshards (and never transfers implicitly).
    with span("seclud.upload") as t_fold:
        cells_spec, seg_spec = sh.plan_specs(sidx.mesh)
        cells_d = jax.device_put(lowered.cells, NamedSharding(sidx.mesh, cells_spec))
        stage_seg_d = jax.device_put(lowered.stage_seg, NamedSharding(sidx.mesh, seg_spec))
    with span("seclud.dispatch"):
        out = fold(sidx.post_docs, sidx.fences, cells_d, stage_seg_d)
    with span("seclud.readback"):
        tally = jax.device_get(out[0])
        counts = tally[: lowered.n_queries].astype(np.int64)
        info["t_fold_s"] = time.perf_counter() - t_fold
    # The survivor totals entering each stage, summed over the shards by
    # the fold's psum.
    entering = tally[lowered.n_queries_pad :]
    stages = [
        {"stage": float(t + 1), "cur_cells": float(c), "cur_live": float(entering[t]),
         "reads": float(r)}
        for t, (c, r) in enumerate(zip(lowered.stage_cells, lowered.stage_reads, strict=True))
    ]
    compiles = float(jit_cache_size(fold) - cache_before)
    total_true = float(lowered.n_cells_true.sum())
    max_true = float(lowered.n_cells_true.max())
    # Per-shard dispatch times for the straggler monitor.  The fused
    # shard_map is a synchronous collective — every shard runs the same
    # unified-shape program and holds the device for the whole fold — so
    # the honest per-shard attribution on a single-process rig is the
    # fold time itself, equal across shards; a real straggler (or an
    # injected one) shows up as that shard's entry inflating.
    shard_times = np.full(lowered.n_shards, info["t_fold_s"], np.float64)
    if fault_hook is not None:
        shard_times = fault_hook.perturb_shard_times(shard_times)
    info.update(
        n_pairs=float(plan.n_pairs),
        n_kernel_calls=1.0,
        n_shards=float(lowered.n_shards),
        shards_touched=float(lowered.shards_touched),
        shard_cells=lowered.n_cells_true.astype(float).tolist(),
        shard_times=[float(x) for x in shard_times],
        agg_throughput=total_true / max(max_true, 1.0),
        load_balance=total_true / max(lowered.n_shards * max_true, 1.0),
        padding_overhead=float(lowered.n_shards * lowered.n_cells)
        / max(total_true, 1.0),
        cells=float(lowered.n_shards * lowered.n_cells),
        cells_true=total_true,
        upload_bytes=float(lowered.cells.nbytes + lowered.stage_seg.nbytes),
        search_reads=float(sum(lowered.stage_reads)),
        stages=stages,
        **_late_info(stages),
        jit_compiles=compiles,
    )
    if not return_docs:
        return counts, info

    # Per-shard members -> original plan-group order: each group's cells
    # sit contiguously inside its owning shard's row; gathering rows in
    # group order and dropping PAD holes restores exactly the
    # single-device (and host-loop) doc array.
    members = jax.device_get(out[1]).reshape(-1)
    starts = lowered.grp_shard * lowered.n_cells + lowered.grp_off
    orig_cells = _ragged_gather(members, starts, lowered.grp_cnt)
    docs = orig_cells[orig_cells != PAD].astype(np.int32)
    return counts, docs, info
