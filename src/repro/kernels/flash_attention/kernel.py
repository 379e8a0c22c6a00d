"""Pallas TPU kernel: blocked FlashAttention (fwd) with causal/window skip.

Standard IO-aware tiling (FlashAttention, adapted to TPU VMEM/MXU):
grid (B·H, Lq/TQ, Lk/TK), online-softmax running (m, l, acc) carried in
VMEM scratch across the contraction (last) grid axis.  Causal and
sliding-window tiles that are fully masked are skipped with ``pl.when``
(block-level sparsity — the same skip structure the gemma3 5:1
local:global pattern exploits at long context).

Tile sizes default to (TQ, TK) = (128, 128); D is kept whole (the MXU
contracts (TQ, D) @ (D, TK) then (TQ, TK) @ (TK, D)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.ref import NEG_INF

__all__ = ["flash_attention_kernel"]


def _kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int | None, off: int, tq: int, tk: int,
    n_k: int,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Block-level skip decision (static per (qi, kj) only when traced with
    # concrete program ids — here dynamic, so use pl.when).
    q_lo = qi * tq + off  # key-aligned position of the first query row
    q_hi = q_lo + tq - 1
    k_lo = kj * tk
    k_hi = k_lo + tk - 1
    live = True
    if causal:
        live = k_lo <= q_hi
    if window is not None:
        live = jnp.logical_and(live, k_hi > q_lo - window)

    @pl.when(live)
    def _():
        q = q_ref[0]  # (TQ, D)
        k = k_ref[0]  # (TK, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (TQ, TK)
        ii = q_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        jj = k_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        mask = jnp.ones((tq, tk), jnp.bool_)
        if causal:
            mask &= jj <= ii
        if window is not None:
            mask &= jj > ii - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # (TQ, 1)
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * alpha + p.sum(axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

    @pl.when(kj == n_k - 1)
    def _():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "tile_q", "tile_k", "interpret"),
)
def flash_attention_kernel(
    q: jnp.ndarray,  # (BH, Lq, D)
    k: jnp.ndarray,  # (BH, Lk, D)
    v: jnp.ndarray,
    causal: bool = True,
    window: int | None = None,
    tile_q: int = 128,
    tile_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, lq, d = q.shape
    _, lk, _ = k.shape
    assert lq % tile_q == 0 and lk % tile_k == 0
    off = lk - lq
    n_k = lk // tile_k
    grid = (bh, lq // tile_q, n_k)
    scale = 1.0 / (d**0.5)

    return pl.pallas_call(
        functools.partial(
            _kernel,
            scale=scale,
            causal=causal,
            window=window,
            off=off,
            tq=tile_q,
            tk=tile_k,
            n_k=n_k,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tile_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, tile_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tile_q, 1), jnp.float32),
            pltpu.VMEM((tile_q, 1), jnp.float32),
            pltpu.VMEM((tile_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
