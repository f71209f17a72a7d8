"""Decoder block composition: (mixer, ffn) specs -> init/apply/decode.

A block = pre-norm mixer + residual, then pre-norm FFN + residual (when the
family has a separate FFN). Mixer types:

    attn        full-attention GQA (window = cfg.sliding_window if set)
    local_attn  sliding-window GQA (window = cfg.local_attn_window)
    mla         DeepSeek-V2 multi-head latent attention
    rec         Griffin RG-LRU recurrent block
    mlstm/slstm xLSTM blocks

FFN types: mlp | moe | none.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import init_mlp, init_rmsnorm, mlp, rmsnorm

BlockSpec = Tuple[str, str]


def _mixer_window(spec_mixer: str, cfg) -> int:
    if spec_mixer == "local_attn":
        return cfg.local_attn_window
    return cfg.sliding_window


def init_block(key, spec: BlockSpec, cfg, dtype=jnp.float32) -> Dict:
    mixer, ffn = spec
    k1, k2 = jax.random.split(key)
    p: Dict = {"norm1": init_rmsnorm(cfg.d_model, dtype)}
    if mixer in ("attn", "local_attn"):
        p["attn"] = attn_mod.init_attention(k1, cfg, dtype)
    elif mixer == "mla":
        p["mla"] = mla_mod.init_mla(k1, cfg, dtype)
    elif mixer == "rec":
        p["rec"] = rglru_mod.init_rglru_block(k1, cfg, dtype)
    elif mixer == "mlstm":
        p["mlstm"] = xlstm_mod.init_mlstm_block(k1, cfg, dtype)
    elif mixer == "slstm":
        p["slstm"] = xlstm_mod.init_slstm_block(k1, cfg, dtype)
    else:
        raise ValueError(mixer)
    if ffn == "mlp":
        p["norm2"] = init_rmsnorm(cfg.d_model, dtype)
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype)
    elif ffn == "moe":
        p["norm2"] = init_rmsnorm(cfg.d_model, dtype)
        p["moe"] = moe_mod.init_moe(k2, cfg, dtype)
    elif ffn != "none":
        raise ValueError(ffn)
    return p


def apply_block(params, x, positions, spec: BlockSpec, cfg):
    """Training/prefill. Returns (x, aux_loss). The mixer, of whatever
    kind, runs under the named scope ``attention``."""
    mixer, ffn = spec
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    with jax.named_scope("attention"):
        if mixer in ("attn", "local_attn"):
            h = attn_mod.attention(params["attn"], h, positions, cfg,
                                   window=_mixer_window(mixer, cfg))
        elif mixer == "mla":
            h = mla_mod.mla_attention(params["mla"], h, positions, cfg)
        elif mixer == "rec":
            h = rglru_mod.rglru_block(params["rec"], h, cfg)
        elif mixer == "mlstm":
            h = xlstm_mod.mlstm_block(params["mlstm"], h, cfg)
        elif mixer == "slstm":
            h = xlstm_mod.slstm_block(params["slstm"], h, cfg)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if ffn == "mlp":
        x = x + mlp(params["mlp"], rmsnorm(params["norm2"], x, cfg.norm_eps), cfg.act)
    elif ffn == "moe":
        h2, aux = moe_mod.moe_ffn(params["moe"], rmsnorm(params["norm2"], x, cfg.norm_eps), cfg)
        x = x + h2
    return x, aux


# --------------------------------------------------------------------------
# Decode
# --------------------------------------------------------------------------

def init_block_cache(spec: BlockSpec, cfg, batch: int, max_len: int,
                     dtype=jnp.bfloat16) -> Dict:
    mixer, _ = spec
    if mixer in ("attn", "local_attn"):
        w = _mixer_window(mixer, cfg)
        return attn_mod.init_cache(cfg, batch, max_len, window=w, dtype=dtype)
    if mixer == "mla":
        return mla_mod.init_mla_cache(cfg, batch, max_len, dtype=dtype)
    if mixer == "rec":
        return rglru_mod.init_rglru_state(cfg, batch)
    if mixer == "mlstm":
        return xlstm_mod.init_mlstm_state(cfg, batch)
    if mixer == "slstm":
        return xlstm_mod.init_slstm_state(cfg, batch)
    raise ValueError(mixer)


def decode_block(params, cache, x, pos, spec: BlockSpec, cfg):
    """Single-token decode. Returns (cache', x)."""
    mixer, ffn = spec
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    if mixer in ("attn", "local_attn"):
        cache, h = attn_mod.decode_attention(
            params["attn"], cache, h, pos, cfg, window=_mixer_window(mixer, cfg))
    elif mixer == "mla":
        cache, h = mla_mod.mla_decode(params["mla"], cache, h, pos, cfg)
    elif mixer == "rec":
        cache, h = rglru_mod.rglru_block_decode(params["rec"], cache, h, cfg)
    elif mixer == "mlstm":
        cache, h = xlstm_mod.mlstm_block_decode(params["mlstm"], cache, h, cfg)
    elif mixer == "slstm":
        cache, h = xlstm_mod.slstm_block_decode(params["slstm"], cache, h, cfg)
    x = x + h
    if ffn == "mlp":
        x = x + mlp(params["mlp"], rmsnorm(params["norm2"], x, cfg.norm_eps), cfg.act)
    elif ffn == "moe":
        h2, _ = moe_mod.moe_ffn(params["moe"], rmsnorm(params["norm2"], x, cfg.norm_eps), cfg)
        x = x + h2
    return cache, x
