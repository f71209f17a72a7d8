"""Plain SmolLM-135M (HuggingFaceTB/SmolLM-135M): a Llama-style decoder.

Written from the model's published description alone: a tied token
embedding, then ``num_layers`` blocks of

    x += Wo . attention(rope(Wq . n1(x)), rope(Wk . n1(x)), Wv . n1(x))
    x += Wdown . (silu(Wgate . n2(x)) * Wup . n2(x))

with RMSNorm ``n(x) = scale * x / sqrt(mean(x^2) + eps)``, causal softmax
attention over ``num_heads`` query heads that share ``num_kv_heads`` key and
value heads (query head h reads key head ``h // (num_heads /
num_kv_heads)``), rotary positions on the two halves of each head
(theta ``rope_theta``), and the logits ``n_final(x) . E^T`` over the whole
vocabulary, each sequence's log-softmax over it written out. No chunked
kernel.

The weights are read in the program's layout (the layers stacked on a
leading axis under ``groups/u0``), one layer at a time.

Departures from a plain forward and backward, each forced by the chip:

* the mean over a minibatch is taken sequence by sequence (``lax.map``),
  so that one sequence's float32 attention and logits live at a time;
* each layer, and each sequence's head, is recomputed in the backward
  (``jax.checkpoint``): without it one sequence's float32 residuals, 30
  layers of 9 x 2,048 x 2,048 attention probabilities among them, take
  about 6.8 GB, and the reference CD-BFL's own state holds about 10 GB of
  the chip at the fourth node's local steps. The recomputed forward is
  the same arithmetic on the same inputs, so no value changes;
* the layers run as a ``lax.scan`` over the stacked weights and not as a
  Python loop: unrolled, the 30 layers of the four local steps made a
  node step the TPU compiler took 9.4 minutes over (a described-v5e
  compile); scanned over layers and mapped over sequences, 2.0 minutes
  and 2.95 GB of temporaries, against 3.9 GB unrolled.

``dtype`` is the precision of the products: float32 at ``highest`` for the
reference; for the control (the configuration's products are bfloat16) the
operands of every product are rounded to float8 (e4m3) and everything is
stored in bfloat16.
"""
from __future__ import annotations

import math
from contextlib import nullcontext
from functools import lru_cache

import jax
import jax.numpy as jnp

FP8 = jnp.dtype("float8_e4m3fn")
INIT_STD = 0.02          # the published initializer_range


def shapes(cfg) -> dict:
    n, d, f, v = (cfg["num_layers"], cfg["d_model"], cfg["d_ff"],
                  cfg["vocab_size"])
    h, kv = cfg["num_heads"], cfg["num_kv_heads"]
    hd = d // h
    return {
        "embed": {"tok": (v, d)},
        "final_norm": {"scale": (d,)},
        "groups": {"u0": {
            "norm1": {"scale": (n, d)},
            "attn": {"wq": (n, d, h, hd), "wk": (n, d, kv, hd),
                     "wv": (n, d, kv, hd), "wo": (n, h, hd, d)},
            "norm2": {"scale": (n, d)},
            "mlp": {"gate": (n, d, f), "up": (n, d, f), "down": (n, f, d)},
        }},
    }


def flat_shapes(cfg) -> list:
    """``[(path, shape)]`` of every weight, in the tree's leaf order."""
    return jax.tree_util.tree_flatten_with_path(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))[0]


def num_params(cfg) -> int:
    return sum(math.prod(s) for _, s in flat_shapes(cfg))


def init(key, cfg):
    """Norm scales one; every other weight normal with the published
    initializer range. Float32."""
    flat = []
    for i, (path, shape) in enumerate(flat_shapes(cfg)):
        if jax.tree_util.keystr(path).endswith("['scale']"):
            flat.append(jnp.ones(shape, jnp.float32))
        else:
            flat.append(INIT_STD * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))
    tdef = jax.tree.structure(shapes(cfg),
                              is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree.unflatten(tdef, flat)


def _ops(dtype):
    """(storage dtype, operand rounding, precision context) for ``dtype``."""
    if jnp.dtype(dtype) == FP8:
        return (jnp.bfloat16,
                lambda a: a.astype(FP8).astype(jnp.bfloat16), nullcontext)
    if jnp.dtype(dtype) == jnp.float32:
        return (jnp.float32, lambda a: a,
                lambda: jax.default_matmul_precision("highest"))
    return dtype, lambda a: a, nullcontext


def _rmsnorm(x, scale, eps):
    return scale * x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                + eps)


def _rope(x, theta):
    """x (S, heads, hd): rotate (x1, x2), the two halves of each head, by
    position times ``theta ** (-2i / hd)``."""
    s, hd = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(ang)[:, None].astype(x.dtype)
    sin = jnp.sin(ang)[:, None].astype(x.dtype)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, op, layer, x):
    """One block on one sequence's ``x (S, D)``."""
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    a, m = layer["attn"], layer["mlp"]
    h = _rmsnorm(x, layer["norm1"]["scale"], eps)
    q = _rope(jnp.einsum("sd,dhk->shk", op(h), op(a["wq"])), theta)
    k = _rope(jnp.einsum("sd,dgk->sgk", op(h), op(a["wk"])), theta)
    v = jnp.einsum("sd,dgk->sgk", op(h), op(a["wv"]))
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s, hd = x.shape[0], q.shape[-1]
    scores = jnp.einsum("shk,thk->hst", op(q), op(k)) / math.sqrt(hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("hst,thk->shk", op(probs), op(v))
    x = x + jnp.einsum("shk,hkd->sd", op(ctx), op(a["wo"]))
    h = _rmsnorm(x, layer["norm2"]["scale"], eps)
    gate = jax.nn.silu(jnp.einsum("sd,df->sf", op(h), op(m["gate"])))
    up = jnp.einsum("sd,df->sf", op(h), op(m["up"]))
    return x + jnp.einsum("sf,fd->sd", op(gate * up), op(m["down"]))


def _head_nll(cfg, op, embed, norm, x, tokens):
    """Mean NLL of one sequence's next tokens from its last hidden states
    ``x (S, D)``: the logits over the whole vocabulary and their
    log-softmax."""
    h = _rmsnorm(x, norm, cfg["norm_eps"])
    logp = jax.nn.log_softmax(jnp.einsum("sd,vd->sv", op(h), op(embed)), -1)
    return -jnp.mean(jnp.take_along_axis(logp[:-1], tokens[1:, None], -1))


def nll(params, batch, dtype=jnp.float32, *, cfg):
    """Mean next-token NLL of ``batch = {"tokens": (B, S)}``: the mean over
    the sequences of each sequence's mean."""
    store, op, precision = _ops(dtype)
    p = jax.tree.map(lambda a: a.astype(store), params)
    layer = jax.checkpoint(lambda x, lp: (_layer(cfg, op, lp, x), None))
    head = jax.checkpoint(lambda x, t: _head_nll(
        cfg, op, p["embed"]["tok"], p["final_norm"]["scale"], x, t))

    def sequence(tokens):
        x, _ = jax.lax.scan(layer, p["embed"]["tok"][tokens],
                            p["groups"]["u0"])
        return head(x, tokens)

    with precision():
        return jnp.mean(jax.lax.map(sequence, batch["tokens"]))


# -- what the harness asks of a reference model ------------------------------

EXAMPLE_FIELD = "tokens"


def init_params(cfg, seed: int):
    """The run's weights, on the device in one jitted call from the seed."""
    from bench import generate
    fn = jax.jit(lambda k: init(k, cfg))
    return fn(generate.key(seed, generate.SALT_INIT))


@lru_cache(maxsize=None)
def _zipf_fn(shape: tuple, vocab: int, exponent: float):
    def make(k):
        """Token ids by rank: id r drawn with probability proportional to
        (r + 1) ** -exponent, by the inverse of the cumulative sum."""
        w = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -exponent
        cdf = jnp.cumsum(w) / jnp.sum(w)
        u = jax.random.uniform(k, shape, jnp.float32)
        return jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1).astype(
            jnp.int32)
    return jax.jit(make)


def make_pool(cfg, traffic, seed: int) -> dict:
    """Per-node token pools ``(K, pool, seq_len)`` int32, Zipf over the
    whole vocabulary."""
    from bench import generate
    shape = (int(traffic["nodes"]), int(traffic["pool"]),
             int(traffic["seq_len"]))
    return {"tokens": _zipf_fn(shape, int(cfg["vocab_size"]),
                               float(traffic["zipf"]))(
        generate.key(seed, generate.SALT_DATA))}


def nll_for(cfg):
    def bound(params, batch, dtype=jnp.float32):
        return nll(params, batch, dtype, cfg=cfg)
    return bound
