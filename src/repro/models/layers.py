"""Shared neural-net building blocks (pure pytrees, jax-only).

Conventions:
  * params are nested dicts of jnp arrays;
  * init functions take an explicit PRNG key and return params;
  * dtypes: params in ``param_dtype`` (fp32 default), activations cast to
    ``dtype`` (bf16 for the production configs);
  * attention is GQA-general: n_q heads grouped over n_kv heads, optional
    QKV bias (Qwen), optional sliding window (gemma3 local layers),
    optional per-head QK-norm (Qwen3/gemma3);
  * decode uses an explicit KV cache pytree, optionally int8-quantized
    with per (position, head) scales (the serving memory optimization
    that lets 32k-context decode fit a v5e pod — EXPERIMENTS.md).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import get_active_mesh

__all__ = [
    "rms_norm",
    "dense_init",
    "dense",
    "rope",
    "attention",
    "gqa_attention_init",
    "gqa_attention_apply",
    "mlp_init",
    "mlp_apply",
    "moe_init",
    "moe_apply",
    "KVCache",
    "init_kv_cache",
]


# ---------------------------------------------------------------------------
# Basics
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def dense_init(key, d_in: int, d_out: int, bias: bool = False, dtype=jnp.float32):
    w = jax.random.normal(key, (d_in, d_out), dtype) * (d_in**-0.5)
    p = {"kernel": w}
    if bias:
        p["bias"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["kernel"].astype(x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10_000.0) -> jnp.ndarray:
    """x (..., L, H, D) rotated by per-position angle; positions (..., L)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (..., L, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., L, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, windowed, cached)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def attention(
    q: jnp.ndarray,  # (B, Lq, Hq, D)
    k: jnp.ndarray,  # (B, Lk, Hkv, D)
    v: jnp.ndarray,  # (B, Lk, Hkv, D)
    causal: bool = True,
    window: Optional[int] = None,
    kv_valid_len: Optional[jnp.ndarray] = None,  # (B,) for cached decode
    q_chunk: Optional[int] = None,
) -> jnp.ndarray:
    """GQA attention; q heads grouped over kv heads. Returns (B, Lq, Hq, D).

    ``q_chunk``: process queries in chunks (python-unrolled) so the
    (Lq, Lk) score tensor never materializes — the pure-jnp analogue of
    the Pallas flash kernel's tiling; XLA reuses the chunk buffers, so
    peak memory is (q_chunk, Lk), and straight-line code keeps
    cost_analysis exact (no while-loop undercount).
    """
    b, lq, hq, d = q.shape
    if q_chunk is not None and lq > q_chunk and lq % q_chunk == 0:
        outs = []
        dep = jnp.zeros((), q.dtype)
        # Nested remat: in the backward pass each chunk's score matrix is
        # recomputed on demand instead of every chunk staying live after
        # the layer-level remat replays the forward (measured: dominates
        # train peak memory without it).
        chunk_fn = jax.checkpoint(
            lambda q_, k_, v_, kvl, off: _attention_chunk(
                q_, k_, v_, causal, window, kvl, q_offset=off, full_lq=lq
            ),
            static_argnums=(4,),
        )
        for c0 in range(0, lq, q_chunk):
            # `dep` (always 0) chains a data dependency between chunks so
            # the scheduler runs them sequentially and reuses the score
            # buffers — without it, straight-line chunks can all be
            # scheduled before any is consumed (measured: 4x peak memory).
            o = chunk_fn(
                q[:, c0 : c0 + q_chunk] + dep, k, v, kv_valid_len, c0
            )
            dep = (o[0, 0, 0, 0] * 0).astype(q.dtype)
            outs.append(o)
        return jnp.concatenate(outs, axis=1)
    return _attention_chunk(
        q, k, v, causal, window, kv_valid_len, q_offset=0, full_lq=lq
    )


def _attention_chunk(
    q, k, v, causal, window, kv_valid_len, *, q_offset: int, full_lq: int
) -> jnp.ndarray:
    b, lq, hq, d = q.shape
    _, lk, hkv, _ = k.shape
    groups = hq // hkv
    qg = q.reshape(b, lq, hkv, groups, d)
    scale = d**-0.5
    s = jnp.einsum("blhgd,bmhd->bhglm", qg, k).astype(jnp.float32) * scale
    off = lk - full_lq
    i = q_offset + jnp.arange(lq)[:, None]
    j = jnp.arange(lk)[None, :]
    mask = jnp.ones((lq, lk), bool)
    if causal:
        mask &= j <= i + off
    if window is not None:
        mask &= j > i + off - window
    mask = mask[None, None, None]  # (1, 1, 1, lq, lk)
    if kv_valid_len is not None:
        valid = jnp.arange(lk)[None, :] < kv_valid_len[:, None]  # (b, lk)
        mask = mask & valid[:, None, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhglm,bmhd->blhgd", p, v)
    return out.reshape(b, lq, hq, d)


def gqa_attention_init(
    key,
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    qkv_bias: bool = False,
    qk_norm: bool = False,
    dtype=jnp.float32,
):
    ks = jax.random.split(key, 4)
    p = {
        "q": dense_init(ks[0], d_model, n_heads * head_dim, qkv_bias, dtype),
        "k": dense_init(ks[1], d_model, n_kv_heads * head_dim, qkv_bias, dtype),
        "v": dense_init(ks[2], d_model, n_kv_heads * head_dim, qkv_bias, dtype),
        "o": dense_init(ks[3], n_heads * head_dim, d_model, False, dtype),
    }
    if qk_norm:
        p["q_norm"] = jnp.zeros((head_dim,), dtype)
        p["k_norm"] = jnp.zeros((head_dim,), dtype)
    return p


@dataclasses.dataclass
class KVCache:
    """Decode cache. ``k``/``v`` are (B, L_max, Hkv, D) in ``store_dtype``;
    int8 stores keep per-(B, L, Hkv) float scales."""

    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: Optional[jnp.ndarray]  # (B, L_max, Hkv) or None
    v_scale: Optional[jnp.ndarray]
    length: jnp.ndarray  # scalar int32 — valid prefix


jax.tree_util.register_pytree_node(
    KVCache,
    lambda c: ((c.k, c.v, c.k_scale, c.v_scale, c.length), None),
    lambda _, t: KVCache(*t),
)


def init_kv_cache(
    batch: int,
    max_len: int,
    n_kv_heads: int,
    head_dim: int,
    dtype=jnp.bfloat16,
    quantized: bool = False,
) -> KVCache:
    store = jnp.int8 if quantized else dtype
    shape = (batch, max_len, n_kv_heads, head_dim)
    scale = (
        jnp.ones((batch, max_len, n_kv_heads), jnp.float32) if quantized else None
    )
    return KVCache(
        k=jnp.zeros(shape, store),
        v=jnp.zeros(shape, store),
        k_scale=scale,
        v_scale=scale,
        length=jnp.zeros((), jnp.int32),
    )


def _quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(B, L, H) symmetric int8; x (B, L, H, D)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def _dequantize(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def cache_update(cache: KVCache, k_new: jnp.ndarray, v_new: jnp.ndarray) -> KVCache:
    """Append (B, Ln, Hkv, D) at cache.length (decode: Ln == 1)."""
    pos = cache.length
    if cache.k_scale is not None:
        kq, ks = _quantize(k_new)
        vq, vs = _quantize(v_new)
        return KVCache(
            k=jax.lax.dynamic_update_slice(cache.k, kq, (0, pos, 0, 0)),
            v=jax.lax.dynamic_update_slice(cache.v, vq, (0, pos, 0, 0)),
            k_scale=jax.lax.dynamic_update_slice(cache.k_scale, ks, (0, pos, 0)),
            v_scale=jax.lax.dynamic_update_slice(cache.v_scale, vs, (0, pos, 0)),
            length=pos + k_new.shape[1],
        )
    store = cache.k.dtype
    return KVCache(
        k=jax.lax.dynamic_update_slice(cache.k, k_new.astype(store), (0, pos, 0, 0)),
        v=jax.lax.dynamic_update_slice(cache.v, v_new.astype(store), (0, pos, 0, 0)),
        k_scale=None,
        v_scale=None,
        length=pos + k_new.shape[1],
    )


def cache_read(cache: KVCache, dtype) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if cache.k_scale is not None:
        return (
            _dequantize(cache.k, cache.k_scale, dtype),
            _dequantize(cache.v, cache.v_scale, dtype),
        )
    return cache.k.astype(dtype), cache.v.astype(dtype)


def gqa_attention_apply(
    p,
    x: jnp.ndarray,  # (B, L, d_model)
    positions: jnp.ndarray,  # (B, L)
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float = 10_000.0,
    causal: bool = True,
    window: Optional[int] = None,
    cache: Optional[KVCache] = None,
    q_chunk: Optional[int] = None,
) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    b, l, _ = x.shape
    q = dense(p["q"], x).reshape(b, l, n_heads, head_dim)
    k = dense(p["k"], x).reshape(b, l, n_kv_heads, head_dim)
    v = dense(p["v"], x).reshape(b, l, n_kv_heads, head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = rope(q, positions, rope_theta)
    k = rope(k, positions, rope_theta)

    if cache is not None:
        if l == 1 and _flash_decode_applicable(cache, b):
            out, cache = _flash_decode(q, k, v, cache, window)
        else:
            cache = cache_update(cache, k, v)
            k_all, v_all = cache_read(cache, x.dtype)
            out = _cached_attention(q, k_all, v_all, positions, window, q_chunk)
    else:
        out = attention(q, k, v, causal=causal, window=window, q_chunk=q_chunk)
    b_, l_, h_, d_ = out.shape
    y = dense(p["o"], out.reshape(b_, l_, h_ * d_))
    return y, cache


def _flash_decode_applicable(cache: KVCache, batch: int) -> bool:
    """Use the split-K shard_map decode when traced under a mesh whose
    'model' axis divides the cache sequence dim (and 'data' divides the
    batch, or batch == 1 and the data axes join the sequence split)."""
    mesh = get_active_mesh()
    if mesh is None or "model" not in mesh.axis_names or mesh.shape["model"] < 2:
        return False
    s_len = cache.k.shape[1]
    dp = [a for a in mesh.axis_names if a != "model"]
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if batch % dp_size == 0:
        return s_len % mesh.shape["model"] == 0
    if batch == 1:
        return s_len % (mesh.shape["model"] * dp_size) == 0
    return False


def _flash_decode(q, k_new, v_new, cache: KVCache, window=None):
    """Split-K (FlashDecoding-style) single-token decode via shard_map.

    The cache's sequence dim is sharded over 'model' (plus the data axes
    when batch == 1).  Every shard: (a) writes the new K/V into its local
    slice iff the write position falls in it, (b) dequantizes and attends
    over its local keys with a local running (m, l, acc), and (c) one
    psum over the sequence-sharding axes combines the partial softmax:

        m = pmax(m_i);  l = Σ l_i e^{m_i − m};  out = Σ acc_i e^{m_i − m} / l

    Per layer this moves O(B·H·D) bytes instead of re-sharding the cache
    (the naive SPMD schedule all-gathered / replicated it — see
    EXPERIMENTS.md §Perf iteration 2).
    """
    from jax.sharding import PartitionSpec as P

    mesh = get_active_mesh()
    b, _, hq, d = q.shape
    s_len, hkv = cache.k.shape[1], cache.k.shape[2]
    dp = tuple(a for a in mesh.axis_names if a != "model")
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    dp_spec = dp if len(dp) > 1 else dp[0]
    if b % dp_size == 0:
        seq_axes: tuple = ("model",)
        b_spec = dp_spec
    else:  # batch = 1 long-context: sequence over every axis
        seq_axes = tuple(list(dp) + ["model"])
        b_spec = None
    seq_spec = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    quantized = cache.k_scale is not None
    groups = hq // hkv

    def inner(q_, kn, vn, kc, vc, ks, vs, length):
        # Local slice offset along the sequence dim (row-major over the
        # sequence-sharding axes; sizes are static from the mesh).
        idx = jnp.zeros((), jnp.int32)
        mul = 1
        for a in reversed(seq_axes):
            idx = idx + jax.lax.axis_index(a) * mul
            mul *= mesh.shape[a]
        s_loc = kc.shape[1]
        start = idx * s_loc

        # (a) conditional local cache write at position `length`.
        rel = jnp.clip(length - start, 0, s_loc - 1)
        hit = (length >= start) & (length < start + s_loc)

        def write(buf, new, scale_buf):
            if quantized:
                nq, nscale = _quantize(new)
                old = jax.lax.dynamic_slice(buf, (0, rel, 0, 0), nq.shape)
                buf = jax.lax.dynamic_update_slice(
                    buf, jnp.where(hit, nq, old), (0, rel, 0, 0)
                )
                olds = jax.lax.dynamic_slice(
                    scale_buf, (0, rel, 0), nscale.shape
                )
                scale_buf = jax.lax.dynamic_update_slice(
                    scale_buf, jnp.where(hit, nscale, olds), (0, rel, 0)
                )
                return buf, scale_buf
            old = jax.lax.dynamic_slice(buf, (0, rel, 0, 0), new.shape)
            buf = jax.lax.dynamic_update_slice(
                buf, jnp.where(hit, new.astype(buf.dtype), old), (0, rel, 0, 0)
            )
            return buf, scale_buf

        kc, ks = write(kc, kn, ks)
        vc, vs = write(vc, vn, vs)

        # (b) local attention over the shard's keys.
        if quantized:
            k_loc = _dequantize(kc, ks, q_.dtype)
            v_loc = _dequantize(vc, vs, q_.dtype)
        else:
            k_loc, v_loc = kc.astype(q_.dtype), vc.astype(q_.dtype)
        bq = q_.shape[0]
        qg = q_.reshape(bq, 1, hkv, groups, d)
        s = jnp.einsum("blhgd,bmhd->bhglm", qg, k_loc).astype(jnp.float32) * (
            d**-0.5
        )  # (b, hkv, g, 1, s_loc)
        pos_abs = start + jnp.arange(s_loc)
        valid = pos_abs <= length
        if window is not None:
            valid &= pos_abs > length - window
        s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
        m_loc = s.max(axis=-1, keepdims=True)
        # (c) combine across sequence shards.
        m_glob = m_loc
        for a in seq_axes:
            m_glob = jax.lax.pmax(m_glob, a)
        p = jnp.exp(s - m_glob)
        l_loc = p.sum(axis=-1, keepdims=True)
        acc = jnp.einsum("bhglm,bmhd->bhgld", p.astype(q_.dtype), v_loc)
        l_glob = l_loc
        acc_glob = acc.astype(jnp.float32)
        for a in seq_axes:
            l_glob = jax.lax.psum(l_glob, a)
            acc_glob = jax.lax.psum(acc_glob, a)
        out = (acc_glob / jnp.maximum(l_glob[..., 0][..., None], 1e-30)).astype(
            q_.dtype
        )  # (b, hkv, g, 1, d)
        out = out.transpose(0, 3, 1, 2, 4).reshape(bq, 1, hq, d)
        return out, kc, vc, ks, vs

    cache_seq_spec5 = P(b_spec, seq_spec, None, None)
    cache_seq_spec4 = P(b_spec, seq_spec, None)
    dummy = jnp.zeros((), jnp.float32)
    ks_in = cache.k_scale if quantized else dummy
    vs_in = cache.v_scale if quantized else dummy
    scale_spec = cache_seq_spec4 if quantized else P()

    def wrapper(q_, kn, vn, kc, vc, ks, vs, length):
        ks_ = ks if quantized else None
        vs_ = vs if quantized else None
        out, kc2, vc2, ks2, vs2 = inner(q_, kn, vn, kc, vc, ks_, vs_, length)
        if not quantized:
            ks2 = vs2 = jnp.zeros((), jnp.float32)
        return out, kc2, vc2, ks2, vs2

    fn = jax.shard_map(
        wrapper,
        mesh=mesh,
        in_specs=(
            P(b_spec, None, None, None),  # q
            P(b_spec, None, None, None),  # k_new
            P(b_spec, None, None, None),  # v_new
            cache_seq_spec5,  # k cache
            cache_seq_spec5,  # v cache
            scale_spec,
            scale_spec,
            P(),  # length
        ),
        out_specs=(
            P(b_spec, None, None, None),
            cache_seq_spec5,
            cache_seq_spec5,
            scale_spec if quantized else P(),
            scale_spec if quantized else P(),
        ),
        check_vma=True,
    )
    out, kc, vc, ks, vs = fn(
        q, k_new, v_new, cache.k, cache.v, ks_in, vs_in, cache.length
    )
    new_cache = KVCache(
        k=kc,
        v=vc,
        k_scale=ks if quantized else None,
        v_scale=vs if quantized else None,
        length=cache.length + 1,
    )
    return out, new_cache


def _cached_attention(q, k_all, v_all, positions, window=None, q_chunk=None):
    """Attention against a (partially filled) cache buffer.

    Key slot j (absolute position j) is visible to the query at absolute
    position p iff ``j <= p`` (causal; also hides unwritten slots) and,
    with a sliding window, ``j > p - window``.  Works for prefill
    (Lq > 1) and single-token decode alike.  ``q_chunk`` as in
    ``attention`` (python-unrolled flash-style query tiling).
    """
    b, lq, hq, d = q.shape
    if q_chunk is not None and lq > q_chunk and lq % q_chunk == 0:
        outs = []
        dep = jnp.zeros((), q.dtype)
        for c0 in range(0, lq, q_chunk):
            o = _cached_attention(
                q[:, c0 : c0 + q_chunk] + dep, k_all, v_all,
                positions[:, c0 : c0 + q_chunk], window, None,
            )
            dep = (o[0, 0, 0, 0] * 0).astype(q.dtype)  # sequentialize (see attention)
            outs.append(o)
        return jnp.concatenate(outs, axis=1)
    lk, hkv = k_all.shape[1], k_all.shape[2]
    groups = hq // hkv
    qg = q.reshape(b, lq, hkv, groups, d)
    s = jnp.einsum("blhgd,bmhd->bhglm", qg, k_all).astype(jnp.float32) * (d**-0.5)
    j = jnp.arange(lk)[None, None, :]
    pos = positions[:, :, None]  # (B, Lq, 1)
    mask = j <= pos
    if window is not None:
        mask &= j > pos - window
    # (B, Lq, Lk) -> (B, 1, 1, Lq, Lk)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhglm,bmhd->blhgd", p, v_all)
    return out.reshape(b, lq, hq, d)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------


def mlp_init(key, d_model: int, d_ff: int, gated: bool = True, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {
        "up": dense_init(ks[0], d_model, d_ff, False, dtype),
        "down": dense_init(ks[1], d_ff, d_model, False, dtype),
    }
    if gated:
        p["gate"] = dense_init(ks[2], d_model, d_ff, False, dtype)
    return p


def mlp_apply(p, x: jnp.ndarray, act: str = "silu") -> jnp.ndarray:
    up = dense(p["up"], x)
    if "gate" in p:
        g = dense(p["gate"], x)
        h = jax.nn.silu(g) * up if act == "silu" else jax.nn.gelu(g) * up
    else:
        h = jax.nn.silu(up) if act == "silu" else jax.nn.gelu(up)
    return dense(p["down"], h)


# ---------------------------------------------------------------------------
# MoE (top-k routing, capacity-bounded sort-based dispatch)
# ---------------------------------------------------------------------------


def moe_init(
    key, d_model: int, d_expert: int, n_experts: int, gated: bool = True,
    dtype=jnp.float32,
):
    ks = jax.random.split(key, 4)
    scale_in = d_model**-0.5
    scale_out = d_expert**-0.5
    p = {
        "router": dense_init(ks[0], d_model, n_experts, False, dtype),
        "up": jax.random.normal(ks[1], (n_experts, d_model, d_expert), dtype)
        * scale_in,
        "down": jax.random.normal(ks[2], (n_experts, d_expert, d_model), dtype)
        * scale_out,
    }
    if gated:
        p["gate"] = (
            jax.random.normal(ks[3], (n_experts, d_model, d_expert), dtype)
            * scale_in
        )
    return p


def moe_apply(
    p,
    x: jnp.ndarray,  # (T, d_model) — flattened tokens
    top_k: int,
    capacity_factor: float = 1.25,
    act: str = "silu",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN. Dispatches to the expert-parallel shard_map path when
    traced under a mesh with a >1 'model' axis (experts are sharded over
    'model' by the LM sharding rules); otherwise the single-device dense
    dispatch below.

    The shard_map path exploits that activations are replicated over
    'model' between blocks (Megatron layout): every expert shard already
    holds every token, so dispatch needs NO all-to-all at all — each shard
    gathers the tokens routed to its local experts and one psum over
    'model' combines the outputs.  (This replaced an XLA-chosen schedule
    that all-gathered the full dispatch buffers; see EXPERIMENTS.md §Perf.)
    """
    mesh = get_active_mesh()
    if (
        mesh is not None
        and "model" in mesh.axis_names
        and mesh.shape["model"] > 1
        and p["up"].shape[0] % mesh.shape["model"] == 0
    ):
        dp_axes = tuple(a for a in mesh.axis_names if a != "model")
        dp_size = 1
        for a in dp_axes:
            dp_size *= mesh.shape[a]
        if x.shape[0] % dp_size == 0:
            return _moe_apply_sharded(
                p, x, top_k, capacity_factor, act, mesh, dp_axes
            )
    return _moe_apply_dense(p, x, top_k, capacity_factor, act)


def _moe_apply_sharded(p, x, top_k, capacity_factor, act, mesh, dp_axes):
    from jax.sharding import PartitionSpec as P

    e = p["router"]["kernel"].shape[1]
    d = x.shape[1]
    n_model = mesh.shape["model"]
    e_loc = e // n_model
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    t_loc = x.shape[0] // dp_size
    capacity = max(8, -(-int(capacity_factor * t_loc * top_k / e) // 8) * 8)
    has_gate = "gate" in p
    dp_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    def inner(router_k, up, gate, down, x_loc):
        m = jax.lax.axis_index("model")
        logits = (x_loc @ router_k.astype(x_loc.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)  # (T_loc, E)
        gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        # Aux loss (identical on every model shard; averaged over data).
        me = probs.mean(axis=0)
        ce = (
            jnp.zeros((e,), jnp.float32).at[gate_idx.reshape(-1)].add(1.0)
            / (t_loc * top_k)
        )
        aux = e * jnp.sum(me * ce)
        for a in dp_axes:
            aux = jax.lax.pmean(aux, a)

        # Local-expert dispatch: this shard owns experts [m·e_loc, (m+1)·e_loc).
        lo = m * e_loc
        flat_e = gate_idx.reshape(-1)
        flat_g = gate_vals.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(t_loc), top_k)
        local = (flat_e >= lo) & (flat_e < lo + e_loc)
        le = jnp.where(local, flat_e - lo, e_loc)  # e_loc = drop group
        order = jnp.argsort(le, stable=True)
        se, st, sg = le[order], flat_t[order], flat_g[order]
        start = jnp.searchsorted(se, jnp.arange(e_loc), side="left")
        rank = jnp.arange(t_loc * top_k) - start[jnp.minimum(se, e_loc - 1)]
        keep = (se < e_loc) & (rank < capacity)
        slot = jnp.where(keep, se * capacity + rank, e_loc * capacity)

        buf = jnp.zeros((e_loc * capacity + 1, d), x_loc.dtype).at[slot].set(
            x_loc[st]
        )
        xe = buf[: e_loc * capacity].reshape(e_loc, capacity, d)
        up_h = jnp.einsum("ecd,edf->ecf", xe, up.astype(x_loc.dtype))
        if has_gate:
            g = jnp.einsum("ecd,edf->ecf", xe, gate.astype(x_loc.dtype))
            h = (jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)) * up_h
        else:
            h = jax.nn.silu(up_h)
        ye = jnp.einsum("ecf,efd->ecd", h, down.astype(x_loc.dtype))
        ye_flat = ye.reshape(e_loc * capacity, d)
        contrib = jnp.where(
            keep[:, None],
            ye_flat[jnp.minimum(slot, e_loc * capacity - 1)] * sg[:, None],
            0.0,
        )
        out = jnp.zeros((t_loc, d), x_loc.dtype).at[st].add(
            contrib.astype(x_loc.dtype)
        )
        # Combine expert shards: one all-reduce over 'model'.
        return jax.lax.psum(out, "model"), aux

    gate_arr = p["gate"] if has_gate else p["up"]  # placeholder, unused
    fn = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            P(),  # router replicated
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
            P(dp_spec, None),
        ),
        out_specs=(P(dp_spec, None), P()),
        check_vma=True,
    )
    return fn(p["router"]["kernel"], p["up"], gate_arr, p["down"], x)


def _moe_apply_dense(p, x, top_k, capacity_factor, act):
    """Single-device sort-based capacity-bounded dispatch (GShard
    semantics). Tokens over capacity are dropped — standard."""
    t, d = x.shape
    e = p["router"]["kernel"].shape[1]
    logits = dense(p["router"], x).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)  # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )

    # Load-balancing aux loss (Switch): e * Σ_e fraction_tokens * mean_prob.
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[gate_idx.reshape(-1)].add(1.0) / (t * top_k)
    aux = e * jnp.sum(me * ce)

    capacity = int(max(1, capacity_factor * t * top_k / e))
    flat_expert = gate_idx.reshape(-1)  # (T*k,)
    flat_gate = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(t), top_k)

    order = jnp.argsort(flat_expert, stable=True)  # group by expert
    se, st_tok, sg = flat_expert[order], flat_token[order], flat_gate[order]
    # rank within expert group
    pos = jnp.arange(t * top_k)
    start = jnp.searchsorted(se, jnp.arange(e), side="left")
    rank = pos - start[se]
    keep = rank < capacity
    slot = jnp.where(keep, se * capacity + rank, e * capacity)  # drop → scratch

    # Gather tokens into (E*C, d) dispatch buffer (+1 scratch row).
    buf = jnp.zeros((e * capacity + 1, d), x.dtype).at[slot].set(x[st_tok])
    xe = buf[: e * capacity].reshape(e, capacity, d)

    up = jnp.einsum("ecd,edf->ecf", xe, p["up"].astype(x.dtype))
    if "gate" in p:
        g = jnp.einsum("ecd,edf->ecf", xe, p["gate"].astype(x.dtype))
        h = (jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)) * up
    else:
        h = jax.nn.silu(up)
    ye = jnp.einsum("ecf,efd->ecd", h, p["down"].astype(x.dtype))  # (E, C, d)

    # Combine: scatter-add weighted expert outputs back to tokens.
    ye_flat = ye.reshape(e * capacity, d)
    contrib = jnp.where(keep[:, None], ye_flat[jnp.minimum(slot, e * capacity - 1)] * sg[:, None], 0.0)
    out = jnp.zeros((t, d), x.dtype).at[st_tok].add(contrib.astype(x.dtype))
    return out, aux
