"""Pallas TPU kernel: δ⁺ scoring SpMM (one-hot-tiled MXU embedding-bag).

The paper's clustering inner loop (per-document δ accumulation, C code)
re-derived for the MXU (DESIGN.md §3): rather than gathering table rows
per term occurrence (random HBM access), the term axis is processed in
tiles of TT. For each (doc block, term tile) the kernel builds the
weighted incidence tile

    W[d, t] = P[tile_base + t] · |{l : ell[d, l] == tile_base + t}|

branch-free on the VPU (one-hot equality over an L-chunk loop, chunked so
the (BD, LC, TT) bool intermediate stays in VMEM), then feeds the MXU:

    out[d, :] += W @ T_tile                     # (BD, TT) @ (TT, K)

Pad slots (ell >= TC) never match a tile and P/T are zero-padded, so
padding contributes nothing. Accumulation runs over the term-tile grid
axis (init at j == 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["cluster_scores_kernel"]


def _kernel(ell_ref, p_ref, t_ref, out_ref, *, tile_t: int, chunk_l: int):
    j = pl.program_id(1)
    ell = ell_ref[...]  # (BD, L) int32
    p = p_ref[...]  # (1, TT) float32
    tbl = t_ref[...]  # (TT, K) float32
    bd, l_pad = ell.shape

    base = j * tile_t
    local = ell - base  # matches iff in [0, TT)
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, tile_t), 2)

    def body(c, w):
        chunk = jax.lax.dynamic_slice(local, (0, c * chunk_l), (bd, chunk_l))
        oh = chunk[:, :, None] == iota  # (BD, LC, TT)
        return w + oh.sum(axis=1).astype(jnp.float32)

    w = jax.lax.fori_loop(
        0, l_pad // chunk_l, body, jnp.zeros((bd, tile_t), jnp.float32)
    )
    acc = jnp.dot(w * p, tbl, preferred_element_type=jnp.float32)  # (BD, K)

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += acc


@functools.partial(
    jax.jit, static_argnames=("block_d", "tile_t", "chunk_l", "interpret")
)
def cluster_scores_kernel(
    ell: jnp.ndarray,
    p: jnp.ndarray,
    tables: jnp.ndarray,
    block_d: int = 16,
    tile_t: int = 128,
    chunk_l: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """out (N, K) = weighted one-hot bag. Pre-padded shapes required:
    N % block_d == 0, L % chunk_l == 0, TC % tile_t == 0 (p/tables
    zero-padded; ell pad value >= TC)."""
    n, l_pad = ell.shape
    tc, k = tables.shape
    assert n % block_d == 0 and l_pad % chunk_l == 0 and tc % tile_t == 0
    assert p.shape == (tc,)

    grid = (n // block_d, tc // tile_t)
    return pl.pallas_call(
        functools.partial(_kernel, tile_t=tile_t, chunk_l=chunk_l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_d, l_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((1, tile_t), lambda i, j: (0, j)),
            pl.BlockSpec((tile_t, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_d, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(ell, p.reshape(1, -1), tables)
