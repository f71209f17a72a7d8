"""Grouped-Query Attention with RoPE, optional QKV bias (Qwen2) and
sliding-window variant (Mistral-style), plus single-token decode with either
a full KV cache or a fixed-size ring-buffer (windowed) cache.

Training/prefill attention takes one of three paths. On a TPU, causal
full attention at a shape the fused kernel takes runs as that kernel
(``repro.kernels.flash_attention``); otherwise long sequences take
``chunked_gqa`` and short ones the naive masked form, which stays the
correctness oracle. ``flash_attention_traces`` and
``chunked_attention_traces`` count the traces that took the first two.

Shapes: x (B, S, D); q (B, S, H, hd); k/v (B, S, KV, hd).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention, interpret_mode
from repro.models.layers import apply_rope, dense_init

NEG_INF = -1e30

# trace-time counters of the paths ``attention`` took
flash_attention_traces = 0
chunked_attention_traces = 0


def init_attention(key, cfg, dtype=jnp.float32) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, (h, hd), dtype=dtype),
        "wk": dense_init(ks[1], d, (kv, hd), dtype=dtype),
        "wv": dense_init(ks[2], d, (kv, hd), dtype=dtype),
        "wo": dense_init(ks[3], h * hd, (d,), dtype=dtype).reshape(h, hd, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((kv, hd), dtype)
        p["bv"] = jnp.zeros((kv, hd), dtype)
    return p


def _qkv(params, x, cfg, positions):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dgk->bsgk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dgk->bsgk", x, params["wv"].astype(dt))
    if "bq" in params:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _gqa_scores(q, k):
    """q (B,Sq,H,hd), k (B,Sk,KV,hd) -> scores (B,KV,H/KV,Sq,Sk)."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, sq, kvh, h // kvh, hd)
    return jnp.einsum("bsgrk,btgk->bgrst", qg, k) / jnp.sqrt(hd).astype(q.dtype)


def _gqa_out(scores, v, params, dt):
    """scores (B,KV,G,Sq,Sk), v (B,Sk,KV,hd) -> (B,Sq,D)."""
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dt)
    ctx = jnp.einsum("bgrst,btgk->bsgrk", probs, v)
    b, sq = ctx.shape[0], ctx.shape[1]
    h = ctx.shape[2] * ctx.shape[3]
    ctx = ctx.reshape(b, sq, h, v.shape[-1])
    return jnp.einsum("bshk,hkd->bsd", ctx, params["wo"].astype(dt))


def attention(params, x, positions, cfg, window: int = 0,
              cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              causal: bool = True):
    """Training/prefill attention. window>0 adds sliding-window banding.

    ``cross_kv`` switches to cross-attention (whisper decoder): keys/values
    are provided and no causal mask is applied.
    """
    global flash_attention_traces, chunked_attention_traces
    dt = x.dtype
    if cross_kv is not None:
        k, v = cross_kv
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
        scores = _gqa_scores(q, k)
        return _gqa_out(scores, v, params, dt)

    q, k, v = _qkv(params, x, cfg, positions)
    s, hd = q.shape[1], q.shape[-1]
    g = q.shape[2] // k.shape[2]
    if (causal and window == 0 and cfg.attn_impl == "auto"
            and not interpret_mode() and flash_attention.supported(s, hd, g)):
        flash_attention_traces += 1
        ctx = flash_attention.flash_gqa(q, k, v)
        return jnp.einsum("bshk,hkd->bsd", ctx, params["wo"].astype(dt))
    use_chunked = causal and (
        cfg.attn_impl == "chunked"
        or (cfg.attn_impl == "auto" and s >= 2 * cfg.chunk_size
            and s % cfg.chunk_size == 0)
    )
    if use_chunked:
        from repro.models.chunked import chunked_gqa
        chunked_attention_traces += 1
        ctx = chunked_gqa(q, k, v, window=window, chunk=cfg.chunk_size)
        return jnp.einsum("bshk,hkd->bsd", ctx, params["wo"].astype(dt))
    scores = _gqa_scores(q, k)
    sq, sk = scores.shape[-2], scores.shape[-1]
    ii = jnp.arange(sq)[:, None]
    jj = jnp.arange(sk)[None, :]
    mask = (jj <= ii) if causal else jnp.ones((sq, sk), bool)
    if window > 0:
        mask = mask & (ii - jj < window)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    return _gqa_out(scores, v, params, dt)


# --------------------------------------------------------------------------
# Decode caches
# --------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, window: int = 0, dtype=jnp.bfloat16):
    """Full cache when window==0, else a ring buffer of ``window`` slots."""
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    slots = window if window > 0 else max_len
    return {
        "k": jnp.zeros((batch, slots, kv, hd), dtype),
        "v": jnp.zeros((batch, slots, kv, hd), dtype),
        "slot_pos": jnp.full((slots,), -1, jnp.int32),
    }


def decode_attention(params, cache, x, pos, cfg, window: int = 0):
    """One decode step. x (B,1,D); pos scalar int32 (same across batch).

    Keys are cached *post-RoPE*, so ring-buffer order never matters: the
    softmax is permutation-invariant given the validity mask.
    """
    dt = x.dtype
    positions = jnp.broadcast_to(pos, (x.shape[0], 1))
    q, k_new, v_new = _qkv(params, x, cfg, positions)

    slots = cache["k"].shape[1]
    slot = jnp.where(window > 0, pos % slots, jnp.minimum(pos, slots - 1))
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
    slot_pos = cache["slot_pos"].at[slot].set(pos)

    scores = _gqa_scores(q, k.astype(dt))                  # (B,KV,G,1,slots)
    valid = (slot_pos >= 0) & (slot_pos <= pos)
    if window > 0:
        valid = valid & (slot_pos > pos - window)
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    out = _gqa_out(scores, v.astype(dt), params, dt)
    return {"k": k, "v": v, "slot_pos": slot_pos}, out
