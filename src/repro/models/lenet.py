"""LeNet classifier for range-azimuth radar maps (the paper's ML model, §IV).

Input: (B, H, W, 1) range-azimuth maps (paper: 256×63); output: R=10 ROI
logits. Sized to ~2.7M trainable parameters at the paper's input resolution
(fc1 width 220 → p ≈ 2.7e6), scaling down gracefully for reduced smoke/bench
variants.

:func:`lenet_predict` is the forward the zoo's ``model.logits`` calls. Under
a ``vmap`` over weight sets that share one input batch (the BMA over a
posterior bank) its batching rule runs :func:`lenet_ensemble_logits`: the
conv tower once for every member, with the member axis packed into the
channel axis. Training differentiates :func:`lenet_logits` directly, since
a ``custom_vmap`` has no reverse mode.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


# Bank members per group of the packed conv2: 8 members x 16 output
# channels fill the 128 lanes of a TPU vector register.
PACK_GROUP = 8


def _conv(x, w, groups=1):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )


def _pool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
    )


def _flat_dim(hw):
    h, w = hw
    h = (h - 4) // 2       # conv5 valid + pool2
    w = (w - 4) // 2
    h = (h - 4) // 2
    w = (w - 4) // 2
    return 16 * h * w


def init_lenet(key, cfg) -> Dict:
    ks = jax.random.split(key, 5)
    fdim = _flat_dim(cfg.input_hw)
    fc1 = max(32, min(220, fdim // 4)) if fdim < 2048 else 220
    return {
        "conv1": {"w": dense_init(ks[0], 25, (6,)).reshape(5, 5, 1, 6),
                  "b": jnp.zeros((6,))},
        "conv2": {"w": dense_init(ks[1], 150, (16,)).reshape(5, 5, 6, 16),
                  "b": jnp.zeros((16,))},
        "fc1": {"w": dense_init(ks[2], fdim, (fc1,)), "b": jnp.zeros((fc1,))},
        "fc2": {"w": dense_init(ks[3], fc1, (84,)), "b": jnp.zeros((84,))},
        "fc3": {"w": dense_init(ks[4], 84, (cfg.num_classes,)),
                "b": jnp.zeros((cfg.num_classes,))},
    }


def _head(params, h):
    """Flattened features (B, F) -> logits (B, R): fc1, fc2, fc3."""
    h = jnp.tanh(h @ params["fc1"]["w"] + params["fc1"]["b"])
    h = jnp.tanh(h @ params["fc2"]["w"] + params["fc2"]["b"])
    return h @ params["fc3"]["w"] + params["fc3"]["b"]


def lenet_logits(params, x) -> jnp.ndarray:
    """x (B, H, W, 1) -> logits (B, R)."""
    h = jnp.tanh(_conv(x, params["conv1"]["w"]) + params["conv1"]["b"])
    h = _pool(h)
    h = jnp.tanh(_conv(h, params["conv2"]["w"]) + params["conv2"]["b"])
    h = _pool(h)
    return _head(params, h.reshape(h.shape[0], -1))


def _pool_packed(x):
    """2x2 max pool, stride 2, of a channel-minor (B, H, W, C) array with
    even H and W, as a max over (H, W) pairs, so that the channels stay the
    minor axis."""
    b, h, w, c = x.shape
    return jnp.max(x.reshape(b, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def _even_out(x, k):
    """Crop x (B, H, W, C) so that a valid k x k convolution of it has even
    H and W: the pool after it drops an odd last row or column anyway."""
    h, w = x.shape[1] - k + 1, x.shape[2] - k + 1
    return x[:, :x.shape[1] - h % 2, :x.shape[2] - w % 2]


def _block_diagonal(w, g):
    """Per-member kernels (M, kh, kw, ci, co) -> one grouped-conv kernel
    (kh, kw, g*ci, M*co): M/g groups of g members, each group's kernel
    block-diagonal over its members, input and output channels
    member-major. The blocks off the diagonal are exact zeros."""
    m, kh, kw, ci, co = w.shape
    w = w.reshape(m // g, g, kh, kw, ci, co).transpose(2, 3, 4, 0, 1, 5)
    own = jnp.eye(g, dtype=bool)[:, None, None, :, None]  # (k, 1, 1, j, 1)
    # bd[h, w, k, i, n, j, o] = w[n, j, h, w, i, o] if k == j else 0
    bd = jnp.where(own, w[:, :, None], jnp.zeros((), w.dtype))
    return bd.reshape(kh, kw, g * ci, m * co)


def _tower_packed(conv, x):
    """Conv params ``{"conv1", "conv2"}`` stacked (M, ...) and one shared x
    (B, H, W, 1) -> flattened features (M, B, F) in the HWC order of
    :func:`lenet_logits`.

    The tower runs once for all M members: conv1 with M*c1 output
    channels, conv2 as one grouped convolution of ``PACK_GROUP`` members a
    group (block-diagonal kernels, so each group fills the lanes), and
    both pools over the packed channel axis. Each pool comes before its
    bias and tanh, which are monotone and so commute with the max: they
    then run on a quarter of the values. The member axis leaves the
    channels once, at the flatten. M not a multiple of the group is padded
    with zero members, dropped before the flatten.
    """
    m = conv["conv1"]["w"].shape[0]
    g = min(PACK_GROUP, m)
    mp = -(-m // g) * g
    conv = jax.tree.map(
        lambda a: jnp.pad(a, [(0, mp - m)] + [(0, 0)] * (a.ndim - 1)), conv)
    c2 = conv["conv2"]["w"].shape[-1]
    with jax.named_scope("bma_packed"):
        w1 = conv["conv1"]["w"]                          # (mp, kh, kw, 1, c1)
        w1 = w1.transpose(1, 2, 3, 0, 4).reshape(w1.shape[1:4] + (-1,))
        k = w1.shape[0]
        h = _pool_packed(_conv(_even_out(x, k), w1))
        h = jnp.tanh(h + conv["conv1"]["b"].reshape(-1))
        h = _pool_packed(_conv(_even_out(h, k),
                               _block_diagonal(conv["conv2"]["w"], g),
                               groups=mp // g))
        h = jnp.tanh(h + conv["conv2"]["b"].reshape(-1))
        b, ho, wo, _ = h.shape
        h = h.reshape(b, ho, wo, mp, c2)[:, :, :, :m]
        h = h.transpose(3, 0, 1, 2, 4).reshape(m, b, ho, wo * c2)
        # Materialise the (W, C) rows before the flatten: left to itself
        # the TPU compiler flattens through a copy with C=16 in the lanes,
        # padded 8x, which reads and writes 1.2 GB more at the paper's size.
        h = jax.lax.optimization_barrier(h)
        return h.reshape(m, b, ho * wo * c2)


@jax.custom_batching.custom_vmap
def _ensemble_tower(conv, x):
    return _tower_packed(conv, x)


@_ensemble_tower.def_vmap
def _ensemble_tower_vmap(axis_size, in_batched, conv, x):
    """An outer vmap over the members' weights (the S of an (S, K, ...)
    bank under nested vmaps) folds into the member axis, so the tower still
    runs once for all S*K members. Only the small conv kernels are
    reshaped; the fc weights keep their layout."""
    conv_batched, x_batched = in_batched
    if x_batched or not all(jax.tree.leaves(conv_batched)):
        axes = jax.tree.map(lambda b: 0 if b else None, conv_batched)
        return jax.vmap(_tower_packed,
                        in_axes=(axes, 0 if x_batched else None),
                        axis_size=axis_size)(conv, x), True
    merged = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), conv)
    h = _ensemble_tower(merged, x)
    return h.reshape((axis_size, -1) + h.shape[1:]), True


def lenet_ensemble_logits(stacked, x) -> jnp.ndarray:
    """Stacked params (M, ...) and one shared x (B, H, W, 1) -> logits
    (M, B, R), the same as ``vmap(lenet_logits, (0, None))``: the packed
    conv tower (:func:`_tower_packed`), then the fc layers as a batched
    matmul per member, as under a plain vmap."""
    h = _ensemble_tower({k: stacked[k] for k in ("conv1", "conv2")}, x)
    return jax.vmap(_head)(stacked, h)


@jax.custom_batching.custom_vmap
def lenet_predict(params, x) -> jnp.ndarray:
    """Forward-only :func:`lenet_logits`; see :func:`_lenet_predict_vmap`."""
    return lenet_logits(params, x)


@lenet_predict.def_vmap
def _lenet_predict_vmap(axis_size, in_batched, params, x):
    """Batched over weight sets with one shared input batch (every param
    leaf batched, ``x`` not): the packed ensemble forward. Otherwise the
    plain per-member vmap."""
    from repro.core.posterior import note_packed_forward
    params_batched, x_batched = in_batched
    if not x_batched and all(jax.tree.leaves(params_batched)):
        note_packed_forward()
        return lenet_ensemble_logits(params, x), True
    axes = jax.tree.map(lambda b: 0 if b else None, params_batched)
    return jax.vmap(lenet_logits, in_axes=(axes, 0 if x_batched else None),
                    axis_size=axis_size)(params, x), True


def lenet_loss(params, batch, key=None):
    """batch: {'x': (B,H,W,1), 'y': (B,)} -> mean CE."""
    logits = lenet_logits(params, batch["x"])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0]
    return jnp.mean(nll), {"logits": logits}
