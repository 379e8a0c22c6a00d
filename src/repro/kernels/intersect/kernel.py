"""Pallas TPU kernels: batched sorted-posting-list intersection.

TPU-native redesign of the paper's Lookup intersection (DESIGN.md §3):
instead of per-element bucket probes (pointer-chasing — poison on TPU),
both sorted lists are processed as lane-wide tiles.  For each short tile
the kernel visits only the long tiles any row of the block could match
and compares every (short, long) element pair there on the VPU.

* **Probe window.**  The per-tile start values of the sorted long rows
  (free: lane 0 of each tile) form a tile directory with monotone
  envelopes ``M_j = max_rows start`` / ``m_j = min_rows start``.  A rank
  count of the short tile's value range [smin, smax] against them — a
  vectorized binary search over the directory — yields the only tiles
  any row could match: ``[last j with M_j <= smin, last j with m_j <=
  smax]``.  With cluster-contiguous reordering (paper §3.3, speedup
  S_R) that is typically one or two tiles.  The windows are computed
  outside the kernel body (:func:`_probe_windows`, plain jnp on the whole
  batch) and reach the kernel as scalar-prefetched SMEM arrays, so the
  loop bounds are scalars and the directory never needs a lane-strided
  gather inside the kernel.
* **All-pairs compare.**  Inside the window each long tile is compared
  with the short tile by rotation: the long tile is rolled one lane at a
  time through all ``tile_s`` offsets and compared lane-for-lane with
  the short tile, so every (short, long) pair meets exactly once.  Each
  step is one (BQ, TS) vreg compare — no 3-D broadcast, no relayout.

Only the LONG rows must be sorted (PAD = int32 max last) — the window
comes from their directory.  Short rows may carry PAD holes anywhere (a
masked k-way fold feeds exactly that); PAD never matches.

Layout: short (B, Ls), long (B, Ll).  Grid (B/BQ, Ls/TS); the long row
block (BQ, Ll) stays resident in VMEM across the short-tile steps and
each long tile is read from it with an aligned ``pl.ds`` slice.
``tile_l`` must be a multiple of ``tile_s``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.intersect.ref import PAD

__all__ = [
    "intersect_count_kernel",
    "intersect_members_kernel",
    "intersect_members_count_kernel",
    "PAD",
]

_NEG = jnp.iinfo(jnp.int32).min


def _probe_windows(short, long, block_q: int, tile_s: int, tile_l: int):
    """Inclusive long-tile window ``[j_lo, j_hi]`` of every grid step
    ``(i, s)``, flattened row-major to ``(B/BQ * Ls/TS,)`` int32 each.

    Masked min/max over the short tile's valid lanes: PAD holes must not
    poison the window.  An all-PAD tile gets smin = PAD, smax = int32 min,
    so ``j_hi = -1`` and the kernel's loop is empty.  PAD-only long tiles
    start at PAD and fall outside both rank counts."""
    b, ls = short.shape
    ll = long.shape[1]
    nb, ns, nl = b // block_q, ls // tile_s, ll // tile_l
    # Reduce lanes first, then rows: one 4-D reduction over both compiles
    # several times slower for the TPU at wide batches.
    valid = short != PAD
    smin = jnp.where(valid, short, PAD).reshape(b, ns, tile_s).min(axis=2)
    smax = jnp.where(valid, short, _NEG).reshape(b, ns, tile_s).max(axis=2)
    smin = smin.reshape(nb, block_q, ns).min(axis=1)  # (nb, ns)
    smax = smax.reshape(nb, block_q, ns).max(axis=1)
    starts = long[:, ::tile_l].reshape(nb, block_q, nl)  # lane 0 of each tile
    upper = starts.max(axis=1)[:, None, :]  # M_j, nondecreasing in j
    lower = starts.min(axis=1)[:, None, :]  # m_j, nondecreasing in j
    j_lo = jnp.maximum((upper <= smin[..., None]).sum(-1) - 1, 0)
    j_hi = (lower <= smax[..., None]).sum(-1) - 1
    return (
        j_lo.reshape(-1).astype(jnp.int32),
        j_hi.reshape(-1).astype(jnp.int32),
    )


def _tile_matches(s_tile, l_tile):
    """(BQ, TS) int32: per short element, how many elements of the long
    tile equal it (PAD lanes of the short tile are masked by the
    caller).  The long tile is cut into ``TL/TS`` chunks; each chunk is
    rolled through all TS lane offsets so every pair is compared once."""
    ts = s_tile.shape[1]
    acc = jnp.zeros(s_tile.shape, jnp.int32)

    def body(_, state):
        rolled, acc = state
        acc = acc + (s_tile == rolled).astype(jnp.int32)
        return pltpu.roll(rolled, 1, 1), acc

    for c in range(l_tile.shape[1] // ts):
        _, acc = jax.lax.fori_loop(
            0, ts, body, (l_tile[:, c * ts : (c + 1) * ts], acc)
        )
    return acc


def _window_matches(lo_ref, hi_ref, short_ref, long_ref, *, tile_l: int):
    """Match counts of this step's short tile over its probe window."""
    step = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    s_tile = short_ref[...]

    def body(j, acc):
        start = pl.multiple_of(j * tile_l, tile_l)
        return acc + _tile_matches(s_tile, long_ref[:, pl.ds(start, tile_l)])

    acc = jax.lax.fori_loop(
        lo_ref[step], hi_ref[step] + 1, body, jnp.zeros(s_tile.shape, jnp.int32)
    )
    return s_tile, jnp.where(s_tile != PAD, acc, 0)


def _accumulate(out_ref, value):
    """Sum ``value`` into the (BQ, 1) output across the short-tile axis."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += value


def _count_kernel(lo_ref, hi_ref, short_ref, long_ref, out_ref, *, tile_l: int):
    _, acc = _window_matches(lo_ref, hi_ref, short_ref, long_ref, tile_l=tile_l)
    _accumulate(out_ref, acc.sum(axis=1, keepdims=True))


def _members_kernel(lo_ref, hi_ref, short_ref, long_ref, out_ref, *, tile_l: int):
    s_tile, acc = _window_matches(lo_ref, hi_ref, short_ref, long_ref, tile_l=tile_l)
    out_ref[...] = jnp.where(acc > 0, s_tile, PAD)


def _members_count_kernel(lo_ref, hi_ref, short_ref, long_ref, out_ref, *, tile_l: int):
    _, acc = _window_matches(lo_ref, hi_ref, short_ref, long_ref, tile_l=tile_l)
    _accumulate(out_ref, (acc > 0).astype(jnp.int32).sum(axis=1, keepdims=True))


def _call(
    kernel_body,
    short,
    long,
    block_q: int,
    tile_s: int,
    tile_l: int,
    interpret: bool,
    per_tile_out: bool,
):
    b, ls = short.shape
    _, ll = long.shape
    assert b % block_q == 0 and ls % tile_s == 0 and ll % tile_l == 0
    assert tile_l % tile_s == 0
    j_lo, j_hi = _probe_windows(short, long, block_q, tile_s, tile_l)
    if per_tile_out:
        out_block = pl.BlockSpec((block_q, tile_s), lambda i, s, lo, hi: (i, s))
        out_shape = jax.ShapeDtypeStruct((b, ls), jnp.int32)
    else:
        out_block = pl.BlockSpec((block_q, 1), lambda i, s, lo, hi: (i, 0))
        out_shape = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    return pl.pallas_call(
        functools.partial(kernel_body, tile_l=tile_l),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b // block_q, ls // tile_s),
            in_specs=[
                pl.BlockSpec((block_q, tile_s), lambda i, s, lo, hi: (i, s)),
                pl.BlockSpec((block_q, ll), lambda i, s, lo, hi: (i, 0)),
            ],
            out_specs=out_block,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(j_lo, j_hi, short, long)


_STATIC = ("block_q", "tile_s", "tile_l", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def intersect_members_kernel(
    short: jnp.ndarray,
    long: jnp.ndarray,
    block_q: int = 8,
    tile_s: int = 128,
    tile_l: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Member docs of ``short_row ∩ long_row`` per row, in place: matched
    elements keep their value, misses become PAD (compaction — sorting
    the PAD holes to the right — is the wrapper's job; rows stay sorted
    so a sort IS a stable left-compaction).  Shapes must be pre-padded
    like :func:`intersect_count_kernel`."""
    return _call(
        _members_kernel, short, long, block_q, tile_s, tile_l, interpret, True
    )


@functools.partial(jax.jit, static_argnames=_STATIC)
def intersect_members_count_kernel(
    short: jnp.ndarray,
    long: jnp.ndarray,
    block_q: int = 8,
    tile_s: int = 128,
    tile_l: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Number of ``short_row`` elements present in ``long_row``, per row —
    the count reduction of the members probe (duplicates in the short
    row each count)."""
    out = _call(
        _members_count_kernel, short, long, block_q, tile_s, tile_l, interpret, False
    )
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def intersect_count_kernel(
    short: jnp.ndarray,
    long: jnp.ndarray,
    block_q: int = 8,
    tile_s: int = 128,
    tile_l: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """|short_row ∩ long_row| per row: the number of equal (short, long)
    element pairs.  Shapes must be pre-padded: B % block_q == 0,
    Ls % tile_s == 0, Ll % tile_l == 0, tile_l % tile_s == 0."""
    out = _call(
        _count_kernel, short, long, block_q, tile_s, tile_l, interpret, False
    )
    return out[:, 0]
