"""Public jit'd wrapper for the batched intersection kernel.

Pads ragged inputs to kernel-aligned shapes and dispatches:

* on TPU        → the Pallas kernel (Mosaic),
* elsewhere     → interpret mode when ``force_kernel`` (tests), else the
                  pure-jnp reference (production CPU path — XLA's fused
                  searchsorted is the right tool off-TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.intersect.kernel import (
    intersect_count_kernel,
    intersect_members_count_kernel,
    intersect_members_kernel,
)
from repro.kernels.intersect.ref import (
    PAD,
    intersect_count_ref,
    intersect_members_ref,
)

__all__ = ["intersect_count", "intersect_members"]


def _pad_to(x: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    return jnp.pad(
        x,
        ((0, rows - x.shape[0]), (0, cols - x.shape[1])),
        constant_values=PAD,
    )


def intersect_count(
    short,
    long,
    block_q: int = 8,
    tile_s: int = 128,
    tile_l: int = 128,
    force_kernel: bool = False,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-row |short ∩ long| for PAD-padded sorted int32 rows (B, *)."""
    short = jnp.asarray(short, jnp.int32)
    long = jnp.asarray(long, jnp.int32)
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or force_kernel):
        return intersect_count_ref(short, long)
    if interpret is None:
        interpret = not on_tpu
    b = int(np.ceil(short.shape[0] / block_q)) * block_q
    ls = int(np.ceil(short.shape[1] / tile_s)) * tile_s
    ll = int(np.ceil(long.shape[1] / tile_l)) * tile_l
    out = intersect_count_kernel(
        _pad_to(short, b, ls),
        _pad_to(long, b, ll),
        block_q=block_q,
        tile_s=tile_s,
        tile_l=tile_l,
        interpret=interpret,
    )
    return out[: short.shape[0]]


def intersect_members(
    short,
    long,
    block_q: int = 8,
    tile_s: int = 128,
    tile_l: int = 128,
    force_kernel: bool = False,
    interpret: bool | None = None,
    reduce: str = "docs",
) -> jnp.ndarray:
    """Members of ``short_row ∩ long_row`` for PAD-padded sorted int32
    rows — the pairwise select step of a k-way intersection fold.

    ``reduce``:
      * ``"docs"``  — (B, Ls) PAD-compacted member docs (survivors
        left-aligned, sorted; PAD fills the rest);
      * ``"mask"``  — (B, Ls) docs *in place*: matches keep their value,
        misses become PAD (what a masked chain stage consumes);
      * ``"count"`` — (B,) int32 |short ∩ long| through the members
        probe's count reduction.

    On TPU the Pallas kernel compares each short tile with the long
    tiles of its probe window (a rank count against the long row's tile
    directory); elsewhere the pure-jnp reference runs (XLA's
    fused searchsorted — the production CPU path), or the kernel in
    interpret mode when ``force_kernel`` (tests).

    Only ``long`` rows must be sorted (PAD last); ``short`` rows may
    carry PAD holes anywhere — the select step of a masked fold feeds
    its own PAD-holed output back in.
    """
    if reduce not in ("docs", "mask", "count"):
        raise ValueError(f"unknown reduce mode {reduce!r}")
    short = jnp.asarray(short, jnp.int32)
    long = jnp.asarray(long, jnp.int32)
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or force_kernel):
        hit = intersect_members_ref(short, long)
        if reduce == "count":
            return hit.sum(axis=1).astype(jnp.int32)
        masked = jnp.where(hit, short, PAD)
        return jnp.sort(masked, axis=1) if reduce == "docs" else masked
    if interpret is None:
        interpret = not on_tpu
    b = int(np.ceil(short.shape[0] / block_q)) * block_q
    ls = int(np.ceil(short.shape[1] / tile_s)) * tile_s
    ll = int(np.ceil(long.shape[1] / tile_l)) * tile_l
    padded_s = _pad_to(short, b, ls)
    padded_l = _pad_to(long, b, ll)
    if reduce == "count":
        out = intersect_members_count_kernel(
            padded_s,
            padded_l,
            block_q=block_q,
            tile_s=tile_s,
            tile_l=tile_l,
            interpret=interpret,
        )
        return out[: short.shape[0]]
    out = intersect_members_kernel(
        padded_s,
        padded_l,
        block_q=block_q,
        tile_s=tile_s,
        tile_l=tile_l,
        interpret=interpret,
    )[: short.shape[0], : short.shape[1]]
    return jnp.sort(out, axis=1) if reduce == "docs" else out
