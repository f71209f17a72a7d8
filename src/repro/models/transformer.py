"""Decoder-only model composition with scan-over-layers.

Deep stacks are lowered as ``lax.scan`` over the *repeating unit* of the
architecture's block pattern (dense: 1 block; RecurrentGemma: (rec, rec,
attn); xLSTM: 7×mLSTM + 1×sLSTM), keeping HLO size O(unit) instead of
O(num_layers). Remainder layers are unrolled as a tail.

The training ``loss`` keeps memory to what a 2,048-token batch can hold
next to the federated state: the scanned layer body runs under
``jax.checkpoint`` (the backward keeps each layer's input and recomputes the
rest), and the cross-entropy over the tied head is taken ``chunk_size``
tokens at a time, each chunk under ``jax.checkpoint``, so the ``(B, S, V)``
float32 logits never exist. Named scopes ``attention`` (every block's
mixer) and ``xent`` (final norm, head product, log-sum-exp and target
gather) mark the two in the compiled program; ``chunked_xent_traces``
counts the traces of the chunked loss.

Public surface (per cfg):
    init(key)                                   -> params
    loss(params, batch, key)                    -> (mean_nll, aux)
    logits(params, batch)                       -> (B, S, V)
    init_decode_state(batch, max_len)           -> cache pytree (zeros)
    decode_step(params, cache, tokens, pos)     -> (cache, logits (B,1,V))
"""
from __future__ import annotations

from functools import partial
from types import SimpleNamespace
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.models import blocks as blk
from repro.models.layers import dense_init, embed_init, init_rmsnorm, rmsnorm


# --------------------------------------------------------------------------
# Block patterns per family
# --------------------------------------------------------------------------

def full_pattern(cfg) -> List[blk.BlockSpec]:
    fam = cfg.family
    L = cfg.num_layers
    if fam in ("dense", "vlm"):
        return [("attn", "mlp")] * L
    if fam == "moe":
        mixer = "mla" if cfg.kv_lora_rank else "attn"
        return [(mixer, "moe")] * L
    if fam == "hybrid":
        unit = tuple(cfg.block_pattern) or ("rec", "rec", "local_attn")
        pat = [(m, "mlp") for m in unit]
        out = (pat * ((L + len(pat) - 1) // len(pat)))[:L]
        return out
    if fam == "ssm":
        r = cfg.mlstm_ratio
        unit = [("mlstm", "none")] * r + [("slstm", "none")]
        return (unit * ((L + len(unit) - 1) // len(unit)))[:L]
    raise ValueError(fam)


def scan_unit(cfg) -> Tuple[List[blk.BlockSpec], int, List[blk.BlockSpec]]:
    """(repeating unit, n_groups, tail specs)."""
    pat = full_pattern(cfg)
    if cfg.family in ("dense", "vlm", "moe"):
        unit = pat[:1]
    elif cfg.family == "hybrid":
        u = tuple(cfg.block_pattern) or ("rec", "rec", "local_attn")
        unit = [(m, "mlp") for m in u]
    else:  # ssm
        unit = [("mlstm", "none")] * cfg.mlstm_ratio + [("slstm", "none")]
    n_groups = len(pat) // len(unit)
    tail = pat[n_groups * len(unit):]
    return unit, n_groups, tail


# --------------------------------------------------------------------------
# Model
# --------------------------------------------------------------------------

def make_model(cfg) -> SimpleNamespace:
    dtype = jnp.dtype(cfg.dtype)
    unit, n_groups, tail = scan_unit(cfg)
    use_scan = cfg.scan_layers and n_groups > 1

    def init(key) -> Dict:
        kemb, klayers, ktail, khead, kimg = jax.random.split(key, 5)
        p: Dict = {
            "embed": {"tok": embed_init(kemb, cfg.vocab_size, cfg.d_model)},
            "final_norm": init_rmsnorm(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = dense_init(khead, cfg.d_model, (cfg.vocab_size,))
        if cfg.family == "vlm" and cfg.num_image_patches:
            p["embed"]["img_proj"] = dense_init(kimg, cfg.d_model, (cfg.d_model,))
        if use_scan:
            gkeys = jax.random.split(klayers, n_groups)

            def init_group(k):
                uks = jax.random.split(k, len(unit))
                return {f"u{i}": blk.init_block(uks[i], unit[i], cfg)
                        for i in range(len(unit))}

            p["groups"] = jax.vmap(init_group)(gkeys)
        else:
            pat = full_pattern(cfg)
            lkeys = jax.random.split(klayers, max(1, len(pat)))
            p["layers"] = [blk.init_block(lkeys[i], pat[i], cfg)
                           for i in range(len(pat))]
        if use_scan and tail:
            tkeys = jax.random.split(ktail, len(tail))
            p["tail"] = [blk.init_block(tkeys[i], tail[i], cfg)
                         for i in range(len(tail))]
        return p

    # -- embedding ---------------------------------------------------------
    def _embed(params, batch):
        tokens = batch["tokens"]
        x = params["embed"]["tok"].astype(dtype)[tokens]
        if cfg.family == "vlm" and cfg.num_image_patches:
            patches = batch["patches"].astype(dtype) @ params["embed"]["img_proj"].astype(dtype)
            x = jnp.concatenate([patches, x], axis=1)
        return x

    def _head(params, x):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        w = (params["embed"]["tok"].T if cfg.tie_embeddings
             else params["lm_head"]).astype(dtype)
        return x @ w

    # -- forward -----------------------------------------------------------
    def _trunk(params, x, remat=False):
        """``remat``: recompute each scanned layer group in the backward,
        keeping only its input."""
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        aux = jnp.zeros((), jnp.float32)
        if use_scan:
            def body(carry, gparams):
                h, a = carry
                for i, spec in enumerate(unit):
                    h, ai = blk.apply_block(gparams[f"u{i}"], h, positions, spec, cfg)
                    a = a + ai
                return (h, a), None

            if remat:
                body = jax.checkpoint(body, prevent_cse=False)
            (x, aux), _ = jax.lax.scan(body, (x, aux), params["groups"])
            for i, spec in enumerate(tail):
                x, ai = blk.apply_block(params["tail"][i], x, positions, spec, cfg)
                aux = aux + ai
        else:
            pat = full_pattern(cfg)
            for i, spec in enumerate(pat):
                x, ai = blk.apply_block(params["layers"][i], x, positions, spec, cfg)
                aux = aux + ai
        return x, aux

    def logits(params, batch):
        x, _ = _trunk(params, _embed(params, batch))
        return _head(params, x)

    def _xent_sum(params, x, targets, weights):
        """Sum of ``weights * (logsumexp(logits) - logits[target])`` over the
        tokens of ``x (N, D)``, the logits of ``chunk_size`` tokens at a
        time. Each chunk's logits are the bf16 (``cfg.dtype``) head product
        taken to float32, as ``_head`` gives them; the head weight enters
        each chunk in its stored dtype, so its gradient sums over the chunks
        in that dtype."""
        n = x.shape[0]
        c = min(cfg.chunk_size, n)
        pad = -n % c
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
            targets = jnp.pad(targets, (0, pad))
            weights = jnp.pad(weights, (0, pad))
        head = (params["embed"]["tok"] if cfg.tie_embeddings
                else params["lm_head"])

        @partial(jax.checkpoint, prevent_cse=False)
        def chunk(norm, head, xc, tc, wc):
            h = rmsnorm(norm, xc, cfg.norm_eps)
            w = head.astype(dtype)
            lg = (h @ (w.T if cfg.tie_embeddings else w)).astype(jnp.float32)
            lse = jax.nn.logsumexp(lg, axis=-1)
            tgt = jnp.take_along_axis(lg, tc[:, None], axis=-1)[:, 0]
            return jnp.sum(wc * (lse - tgt))

        def body(total, xs):
            return total + chunk(params["final_norm"], head, *xs), None

        total, _ = jax.lax.scan(
            body, jnp.zeros((), jnp.float32),
            (x.reshape(-1, c, x.shape[-1]), targets.reshape(-1, c),
             weights.reshape(-1, c)))
        return total

    def loss(params, batch, key=None):
        model.chunked_xent_traces += 1
        x, aux = _trunk(params, _embed(params, batch), remat=True)
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = x[:, x.shape[1] - s:]               # only text positions
        # position t predicts token t + 1; the last position predicts none
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
        mask = batch.get("loss_mask")
        m = (jnp.ones((b, s - 1), jnp.float32) if mask is None
             else mask[:, 1:].astype(jnp.float32))
        weights = jnp.concatenate([m, jnp.zeros((b, 1), jnp.float32)], axis=1)
        with jax.named_scope("xent"):
            total = _xent_sum(params, x.reshape(b * s, -1),
                              targets.reshape(-1), weights.reshape(-1))
        mean_nll = total / jnp.maximum(jnp.sum(m), 1.0)
        return mean_nll + aux, {"nll": mean_nll, "aux": aux}

    # -- decode ------------------------------------------------------------
    def init_decode_state(batch_size: int, max_len: int, dtype_kv=jnp.bfloat16):
        def unit_cache(spec):
            return blk.init_block_cache(spec, cfg, batch_size, max_len, dtype_kv)
        if use_scan:
            cache = {
                "groups": {
                    f"u{i}": jax.tree.map(
                        lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape).copy(),
                        unit_cache(spec))
                    for i, spec in enumerate(unit)
                },
            }
            if tail:
                cache["tail"] = [unit_cache(spec) for spec in tail]
            return cache
        pat = full_pattern(cfg)
        return {"layers": [unit_cache(spec) for spec in pat]}

    def decode_step(params, cache, tokens, pos):
        """tokens (B, 1) -> (cache', logits (B, 1, V)). pos: scalar int32."""
        x = params["embed"]["tok"].astype(dtype)[tokens]
        if use_scan:
            def body(h, xs):
                gparams, gcache = xs
                new_caches = {}
                for i, spec in enumerate(unit):
                    c, h = blk.decode_block(gparams[f"u{i}"], gcache[f"u{i}"],
                                            h, pos, spec, cfg)
                    new_caches[f"u{i}"] = c
                return h, new_caches

            x, new_group_cache = jax.lax.scan(
                body, x, (params["groups"], cache["groups"]))
            new_cache = {"groups": new_group_cache}
            if tail:
                tc = []
                for i, spec in enumerate(tail):
                    c, x = blk.decode_block(params["tail"][i], cache["tail"][i],
                                            x, pos, spec, cfg)
                    tc.append(c)
                new_cache["tail"] = tc
        else:
            pat = full_pattern(cfg)
            lc = []
            for i, spec in enumerate(pat):
                c, x = blk.decode_block(params["layers"][i], cache["layers"][i],
                                        x, pos, spec, cfg)
                lc.append(c)
            new_cache = {"layers": lc}
        return new_cache, _head(params, x)

    model = SimpleNamespace(
        cfg=cfg, init=init, loss=loss, logits=logits,
        init_decode_state=init_decode_state, decode_step=decode_step,
        pattern=full_pattern(cfg), scan_unit=(unit, n_groups, tail),
        chunked_xent_traces=0,
    )
    return model
