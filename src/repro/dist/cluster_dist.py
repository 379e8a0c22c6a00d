"""Mesh-sharded SeCluD K-means: the paper's §3.2 parallelization sketch
(documents sharded, counts replicated) as a ``shard_map`` program.

Each device holds a row-shard of the ELL-packed frequent-term view.  One
round is:

  local counts  →  psum over the data axes  →  ψ + δ⁺ tables (computed
  redundantly on every shard — they are (k, TC), tiny next to the docs)
  →  local scores  →  local argmin.

The host drives rounds exactly like ``repro.core.kmeans.kmeans``: accept a
round iff ψ improved, stop below the 1 % relative-improvement threshold
(paper §4), reseed empty clusters from the worst-fitting documents.

``distributed_kmeans_fn`` adapts this to the ``kmeans(view, k, ...)``
signature so ``multilevel_cluster`` / ``topdown_cluster`` can run their
large levels on the mesh and their small recursion leaves on the host
(document-grained mode, which is inherently sequential).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.jax_ops import (
    counts_from_ell,
    delta_add_tables_jax,
    ell_pack,
    psi_jax,
    scores_from_ell,
)
from repro.core.kmeans import KMeansResult, kmeans
from repro.core.objective import FrequentTermView, cluster_counts, psi_from_counts
from repro.dist.sharding import axes_size, batch_axes, data_spec

__all__ = ["distributed_kmeans", "distributed_kmeans_fn", "make_round_fn"]


def make_round_fn(mesh, k: int, tc: int, block: int = 512) -> Callable:
    """jit(shard_map) computing one round: (ell, assign, p) -> (assign', ψ).

    ``ell`` rows (documents) are sharded over the data axes and replicated
    over ``model``; the returned assignment is sharded the same way and ψ is
    fully replicated (one psum over the data axes makes the counts — and
    everything derived from them — identical on every shard).
    """
    dp_axes = batch_axes(mesh)
    dp = data_spec(mesh)

    def local_round(ell_loc, assign_loc, p):
        counts = counts_from_ell(ell_loc, assign_loc, k, tc)
        counts = jax.lax.psum(counts, dp_axes)
        psi = psi_jax(counts, p)
        tables = delta_add_tables_jax(counts, p)
        scores = scores_from_ell(ell_loc, tables, p, block=block)
        return jnp.argmin(scores, axis=1).astype(assign_loc.dtype), psi

    # check_vma=False: the psum over the data axes is what establishes
    # the replication of ψ; the nested jit'd ops carry no varying-axis
    # types for the checker to follow.
    fn = jax.shard_map(
        local_round,
        mesh=mesh,
        in_specs=(P(dp, None), P(dp), P()),
        out_specs=(P(dp), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def _reseed_empty_random(
    assign: np.ndarray, k: int, rng: np.random.Generator
) -> np.ndarray:
    """Give each empty cluster one document from the largest cluster."""
    sizes = np.bincount(assign, minlength=k)
    for j in np.flatnonzero(sizes == 0):
        donor = int(np.argmax(sizes))
        cand = np.flatnonzero(assign == donor)
        if len(cand) <= 1:
            break
        d = rng.choice(cand)
        assign[d] = j
        sizes[donor] -= 1
        sizes[j] += 1
    return assign


def distributed_kmeans(
    view: FrequentTermView,
    k: int,
    mesh,
    init_assign: Optional[np.ndarray] = None,
    max_iters: int = 50,
    min_rel_improvement: float = 0.01,
    seed: int = 0,
    block: int = 512,
    l_pad: Optional[int] = None,
) -> Tuple[np.ndarray, float]:
    """Round-based K-means on the ψ objective, documents sharded over the
    mesh's data axes.  Returns ``(assign, psi)`` — ψ as reported by the
    device round *before* the last accepted move (same convention as the
    host driver's history)."""
    assign, psi_dev, _ = _run_rounds(
        view, k, mesh, init_assign, max_iters, min_rel_improvement, seed,
        block, l_pad,
    )
    return assign, psi_dev


def _run_rounds(
    view: FrequentTermView,
    k: int,
    mesh,
    init_assign: Optional[np.ndarray],
    max_iters: int,
    min_rel_improvement: float,
    seed: int,
    block: int,
    l_pad: Optional[int],
) -> Tuple[np.ndarray, float, list]:
    """(assign, device ψ, host ψ history — one entry per accepted round)."""
    n = view.n_docs
    ell, _ = ell_pack(view, l_pad)
    dp_size = axes_size(mesh, data_spec(mesh))
    pad = (-n) % max(dp_size, 1)
    if pad:
        # Padding documents carry only pad slots (rank == tc): they add
        # nothing to any cluster's counts, so their assignment is inert.
        ell = np.concatenate(
            [ell, np.full((pad, ell.shape[1]), view.tc, ell.dtype)]
        )
    p32 = np.asarray(view.p_freq, np.float32)

    rng = np.random.default_rng(seed)
    if init_assign is None:
        assign = (rng.permutation(n + pad) % k).astype(np.int32)
    else:
        assign = np.concatenate(
            [np.asarray(init_assign, np.int32), np.zeros(pad, np.int32)]
        )

    round_fn = make_round_fn(mesh, k, view.tc, block=block)
    psi = psi_from_counts(cluster_counts(view, assign[:n].astype(np.int64), k), view.p_freq)
    psi_dev = float(psi)
    history = [psi]
    # The corpus and P never change across rounds — upload once.
    ell_dev = jnp.asarray(ell)
    p_dev = jnp.asarray(p32)
    for _ in range(max_iters):
        new_assign, psi_round = round_fn(ell_dev, jnp.asarray(assign), p_dev)
        new_assign = np.array(new_assign)  # copy: device arrays are read-only
        new_assign[:n] = _reseed_empty_random(new_assign[:n], k, rng)
        psi_new = psi_from_counts(
            cluster_counts(view, new_assign[:n].astype(np.int64), k), view.p_freq
        )
        if psi_new >= psi * (1.0 - 1e-12):
            break
        improved = (psi - psi_new) / max(psi, 1e-30)
        assign, psi, psi_dev = new_assign, psi_new, float(psi_round)
        history.append(psi)
        if improved < min_rel_improvement:
            break
    return assign[:n].astype(np.int64), psi_dev, history


def distributed_kmeans_fn(
    mesh,
    doc_grained_below: int = 2_048,
    block: int = 512,
) -> Callable[..., KMeansResult]:
    """A drop-in ``kmeans_fn`` for ``multilevel_cluster``/``topdown_cluster``:
    large levels run mesh-sharded, small ones on the host (the
    document-grained mode is sequential by construction)."""

    def fn(
        view: FrequentTermView,
        k: int,
        init_assign: Optional[np.ndarray] = None,
        max_iters: int = 100,
        min_rel_improvement: float = 0.01,
        doc_grained_below: int = doc_grained_below,
        seed: int = 0,
    ) -> KMeansResult:
        if view.n_docs < doc_grained_below:
            return kmeans(
                view, k, init_assign=init_assign, max_iters=max_iters,
                min_rel_improvement=min_rel_improvement,
                doc_grained_below=doc_grained_below, seed=seed,
            )
        assign, _, history = _run_rounds(
            view, k, mesh, init_assign, max_iters, min_rel_improvement,
            seed, block, None,
        )
        return KMeansResult(
            assign=assign, psi=history[-1], n_iters=len(history) - 1,
            psi_history=history,
        )

    return fn
