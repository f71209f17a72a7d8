"""Memory-bounded (chunked) training-path primitives.

Naive attention materializes (B, H, S, S) scores — 825 TB for
mistral-large at train_4k — and the recurrent blocks' scan residuals are
similarly O(S) fp32. These chunked forms bound live memory to
O(chunk · S) (attention) or O(chunk) (recurrences), with
``jax.checkpoint`` making the backward recompute per chunk. ``chunked_gqa``
is the long-sequence attention path off the TPU, and on it for MLA and
windowed attention; causal full attention on a TPU takes the fused kernel
in ``repro.kernels.flash_attention`` instead. Each query chunk's float32
scores against every key, the masked ones too, are written to HBM. The
recurrences take the GLA-style chunkwise mLSTM form. The naive forms in
attention.py/xlstm.py remain the correctness oracles, and the
naive→chunked delta is quantified in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Chunked causal GQA attention (flash-style, q-chunk scan)
# --------------------------------------------------------------------------

def chunked_gqa(q, k, v, *, window: int = 0, chunk: int = 512):
    """q (B,S,H,hd), k/v (B,S,KV,hd) -> (B,S,H,hd). Causal (+ window).

    Scans over query chunks; each chunk attends to all keys with the
    causal/window mask. Scores for one chunk are (B,KV,G,C,S) — transient,
    recomputed in backward via checkpoint.
    """
    b, s, h, hd = q.shape
    kv = k.shape[2]
    vd = v.shape[-1]                                     # MLA: vd != hd
    g = h // kv
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    qg = q.reshape(b, nc, chunk, kv, g, hd).transpose(1, 0, 2, 3, 4, 5)

    from repro.models.sharding_hints import BATCH_AXES, MODEL_AXIS, hint
    k = hint(k, BATCH_AXES, None, MODEL_AXIS, None)
    v = hint(v, BATCH_AXES, None, MODEL_AXIS, None)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one_chunk(qc, ci):
        # qc (B,C,KV,G,hd); keys: all S (masked).
        # re-anchor batch/head sharding: scan restacking loses it (§Perf #1)
        qc = hint(qc, BATCH_AXES, None, MODEL_AXIS, None, None)
        scores = jnp.einsum("bcgrk,btgk->bgrct", qc, k) / jnp.sqrt(hd).astype(q.dtype)
        scores = hint(scores, BATCH_AXES, MODEL_AXIS, None, None, None)
        qpos = ci * chunk + jnp.arange(chunk)            # (C,)
        kpos = jnp.arange(s)                             # (S,)
        mask = kpos[None, :] <= qpos[:, None]
        if window > 0:
            mask = mask & (qpos[:, None] - kpos[None, :] < window)
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
        out = jnp.einsum("bgrct,btgk->bcgrk", probs, v)
        return hint(out, BATCH_AXES, None, MODEL_AXIS, None, None)

    def body(_, xs):
        qc, ci = xs
        return (), one_chunk(qc, ci)

    _, out = jax.lax.scan(body, (), (qg, jnp.arange(nc)))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, vd)
    return out


# --------------------------------------------------------------------------
# Chunked RG-LRU linear recurrence
# --------------------------------------------------------------------------

def chunked_lru(a, bvals, *, chunk: int = 512):
    """Diagonal linear recurrence h_t = a_t h_{t-1} + b_t, chunked.

    a/bvals (B,S,C) fp32. Outer scan carries h; within a chunk an
    associative scan runs under checkpoint. Live memory O(B·chunk·C).
    """
    b, s, c = a.shape
    assert s % chunk == 0
    nc = s // chunk
    a_r = a.reshape(b, nc, chunk, c).transpose(1, 0, 2, 3)
    b_r = bvals.reshape(b, nc, chunk, c).transpose(1, 0, 2, 3)

    from repro.models.sharding_hints import BATCH_AXES, hint

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one_chunk(h0, ac, bc):
        ac = hint(ac, BATCH_AXES)
        bc = hint(bc, BATCH_AXES)
        def combine(x, y):
            a1, b1 = x
            a2, b2 = y
            return a1 * a2, a2 * b1 + b2
        a_cum, b_scan = jax.lax.associative_scan(combine, (ac, bc), axis=1)
        h = a_cum * h0[:, None] + b_scan
        return h[:, -1], h

    def body(h0, xs):
        ac, bc = xs
        return one_chunk(h0, ac, bc)

    h_last, hs = jax.lax.scan(body, jnp.zeros((b, c), a.dtype), (a_r, b_r))
    return hs.transpose(1, 0, 2, 3).reshape(b, s, c)


# --------------------------------------------------------------------------
# Chunkwise mLSTM (inter-chunk recurrent state + intra-chunk parallel)
# --------------------------------------------------------------------------

def chunkwise_mlstm(q, k, v, log_i, log_f, *, chunk: int = 256):
    """q/k/v (B,S,H,hd); log_i/log_f (B,S,H) fp32. Returns (B,S,H,hd).

    Stabilized chunkwise form: the carry is (C (B,H,hd,hd), n (B,H,hd),
    m (B,H)); within a chunk the quadratic form runs on chunk×chunk
    decay matrices only.
    """
    b, s, h, hd = q.shape
    assert s % chunk == 0
    nc = s // chunk
    shp = (nc, b, chunk, h)

    def rs(x):
        return x.reshape(b, nc, chunk, *x.shape[2:]).transpose(1, 0, 2, *range(3, x.ndim + 1))

    qs, ks, vs = rs(q), rs(k), rs(v)
    lis, lfs = rs(log_i), rs(log_f)
    scale = 1.0 / jnp.sqrt(hd)

    from repro.models.sharding_hints import BATCH_AXES, hint

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one_chunk(carry, qc, kc, vc, li, lf):
        qc, kc, vc = hint(qc, BATCH_AXES), hint(kc, BATCH_AXES), hint(vc, BATCH_AXES)
        C0, n0, m0 = carry                               # (B,H,hd,hd),(B,H,hd),(B,H)
        fcum = jnp.cumsum(lf, axis=1)                    # (B,C,H) inclusive
        ftot = fcum[:, -1]                               # (B,H)

        # intra-chunk decay matrix D[t,s] = fcum_t - fcum_s + li_s (s<=t)
        dmat = fcum[:, :, None, :] - fcum[:, None, :, :] + li[:, None, :, :]
        tt, ss_ = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
        dmat = jnp.where((ss_ <= tt)[None, :, :, None], dmat, -jnp.inf)
        # inter decay per row: fcum_t + m0
        inter = fcum + m0[:, None, :]                    # (B,C,H)
        m_row = jnp.maximum(jnp.max(dmat, axis=2), inter)  # (B,C,H)
        m_row = jnp.maximum(m_row, 0.0)

        dexp = jnp.exp(dmat - m_row[:, :, None, :])      # (B,C,C,H)
        inter_w = jnp.exp(inter - m_row)                 # (B,C,H)

        sc = jnp.einsum("bthk,bshk->btsh", qc, kc).astype(jnp.float32) * scale
        w = sc * dexp                                    # (B,C,C,H)
        # intra numerator / denominator
        num_intra = jnp.einsum("btsh,bshk->bthk", w.astype(qc.dtype), vc)
        den_intra = jnp.sum(w, axis=2)                   # (B,C,H)
        # inter: q · C0, q · n0
        qf = qc.astype(jnp.float32) * scale
        num_inter = jnp.einsum("bthk,bhkv->bthv", qf, C0) * inter_w[..., None]
        den_inter = jnp.einsum("bthk,bhk->bth", qf, n0) * inter_w

        den = jnp.maximum(jnp.abs(den_intra + den_inter), jnp.exp(-m_row))
        hout = (num_intra.astype(jnp.float32) + num_inter) / (den[..., None] + 1e-6)

        # ---- state update for next chunk ----
        m_next = jnp.maximum(ftot + m0, jnp.max(ftot[:, None] - fcum + li, axis=1))
        # per-step weight for (k_s v_s): exp(ftot - fcum_s + li_s - m_next)
        kw = jnp.exp(ftot[:, None] - fcum + li - m_next[:, None])   # (B,C,H)
        C1 = (jnp.exp(ftot + m0 - m_next)[..., None, None] * C0
              + jnp.einsum("bsh,bshk,bshv->bhkv", kw,
                           kc.astype(jnp.float32), vc.astype(jnp.float32)))
        n1 = (jnp.exp(ftot + m0 - m_next)[..., None] * n0
              + jnp.einsum("bsh,bshk->bhk", kw, kc.astype(jnp.float32)))
        return (C1, n1, m_next), hout.astype(qc.dtype)

    def body(carry, xs):
        qc, kc, vc, li, lf = xs
        return one_chunk(carry, qc, kc, vc, li, lf)

    carry0 = (
        jnp.zeros((b, h, hd, hd), jnp.float32),
        jnp.zeros((b, h, hd), jnp.float32),
        jnp.zeros((b, h), jnp.float32),
    )
    _, hs = jax.lax.scan(body, carry0, (qs, ks, vs, lis, lfs))
    return hs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)
