"""Plain LeNet for range-azimuth radar maps (Barbieri et al. 2024, §IV).

Written from the paper and LeCun et al. 1998 alone: two 5x5 valid
convolutions (``conv1``, ``conv2`` channels) each followed by tanh and a 2x2
max pool, then ``fc1``, ``fc2`` with tanh and a linear ``fc3`` to the
classes. Sizes come from the configuration file. ``dtype`` is the precision
of the products: float32 at ``highest`` for the reference; for the control
(float32 at default precision, which is one bfloat16 pass on a TPU) the
operands are rounded to float8 (e4m3) and everything is stored in
bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.dtype("float8_e4m3fn")


def flat_dim(cfg) -> int:
    h, w = cfg["input_hw"]
    k = cfg["kernel"]
    for _ in range(2):
        h, w = (h - k + 1) // 2, (w - k + 1) // 2
    return cfg["conv2_channels"] * h * w


def shapes(cfg) -> dict:
    k, c1, c2 = cfg["kernel"], cfg["conv1_channels"], cfg["conv2_channels"]
    f1, f2, r = cfg["fc1"], cfg["fc2"], cfg["num_classes"]
    return {
        "conv1": {"w": (k, k, 1, c1), "b": (c1,)},
        "conv2": {"w": (k, k, c1, c2), "b": (c2,)},
        "fc1": {"w": (flat_dim(cfg), f1), "b": (f1,)},
        "fc2": {"w": (f1, f2), "b": (f2,)},
        "fc3": {"w": (f2, r), "b": (r,)},
    }


def num_params(cfg) -> int:
    return sum(math.prod(s) for layer in shapes(cfg).values()
               for s in layer.values())


def init(key, cfg):
    """Fan-in scaled normal weights, zero biases, float32."""
    out = {}
    for i, (name, layer) in enumerate(sorted(shapes(cfg).items())):
        w = layer["w"]
        fan_in = math.prod(w[:-1])
        kk = jax.random.fold_in(key, i)
        out[name] = {"w": jax.random.normal(kk, w, jnp.float32)
                     / math.sqrt(fan_in),
                     "b": jnp.zeros(layer["b"], jnp.float32)}
    return out


def _ops(dtype):
    """(storage dtype, operand rounding, precision) for ``dtype``."""
    if jnp.dtype(dtype) == FP8:
        return jnp.bfloat16, lambda a: a.astype(FP8).astype(jnp.bfloat16), None
    if jnp.dtype(dtype) == jnp.float32:
        return jnp.float32, lambda a: a, HIGHEST
    return dtype, lambda a: a, None


def logits(params, x, dtype=jnp.float32):
    """``x (B, H, W, 1) -> (B, classes)``."""
    store, op, prec = _ops(dtype)
    p = jax.tree.map(lambda a: a.astype(store), params)
    h = x.astype(store)
    for name in ("conv1", "conv2"):
        h = jax.lax.conv_general_dilated(
            op(h), op(p[name]["w"]), (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
            preferred_element_type=store)
        h = jnp.tanh(h + p[name]["b"])
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    h = h.reshape(h.shape[0], -1)
    for name in ("fc1", "fc2"):
        h = jnp.tanh(jnp.dot(op(h), op(p[name]["w"]), precision=prec,
                             preferred_element_type=store) + p[name]["b"])
    return jnp.dot(op(h), op(p["fc3"]["w"]), precision=prec,
                   preferred_element_type=store) + p["fc3"]["b"]


def nll(params, batch, dtype=jnp.float32):
    """Mean cross-entropy of ``batch = {"x", "y"}``."""
    lg = logits(params, batch["x"], dtype)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], -1))


def probs(params, x, dtype=jnp.float32):
    return jax.nn.softmax(logits(params, x, dtype), axis=-1)


# -- what the harness asks of a reference model ------------------------------

EXAMPLE_FIELD = "y"


def init_params(cfg, seed: int):
    """The run's weights, on the device in one jitted call from the seed."""
    from bench import generate
    fn = jax.jit(lambda k: init(k, cfg))
    return fn(generate.key(seed, generate.SALT_INIT))


def make_pool(cfg, traffic, seed: int) -> dict:
    from bench import generate
    x, y = generate.radar_pool(seed, int(traffic["nodes"]),
                               int(traffic["pool"]), cfg["input_hw"],
                               cfg["num_classes"])
    return {"x": x, "y": y}


def nll_for(cfg):
    return nll


def _bank_fn(cfg, samples: int, nodes: int, spread: float, gain: float):
    """A posterior bank ``(S, K, ...)``: the seed's weights, the output
    layer's times ``gain`` (a confident classifier), plus ``spread`` times
    each weight's init scale (0.1 for biases) of Gaussian noise per sample
    and node."""
    def make(k_init, k_bank):
        theta = init(k_init, cfg)
        theta["fc3"] = jax.tree.map(lambda a: a * gain, theta["fc3"])
        leaves, tdef = jax.tree.flatten_with_path(theta)
        keys = jax.random.split(k_bank, len(leaves))
        out = []
        for kk, (path, a) in zip(keys, leaves):
            scale = (1.0 / math.sqrt(math.prod(a.shape[:-1]))
                     if jax.tree_util.keystr(path).endswith("['w']") else 0.1)
            eps = jax.random.normal(kk, (samples, nodes) + a.shape,
                                    jnp.float32)
            if "fc3" in jax.tree_util.keystr(path):
                scale *= gain
            out.append(a[None, None] + jnp.float32(spread * scale) * eps)
        return jax.tree.unflatten(tdef, out)
    return jax.jit(make)


def make_bank(cfg, seed: int, samples: int, nodes: int, spread: float,
              gain: float = 1.0):
    from bench import generate
    return _bank_fn(cfg, samples, nodes, spread, gain)(
        generate.key(seed, generate.SALT_INIT),
        generate.key(seed, generate.SALT_BANK))


def make_frames(cfg, seed: int, n: int):
    from bench import generate
    return generate.radar_maps(seed, generate.SALT_FRAMES, n,
                               cfg["input_hw"], cfg["num_classes"])[0]


def bma(cfg, seed: int, samples: int, nodes: int, spread: float, gain: float,
        x, dtype=jnp.float32):
    """Mean over every sample and node of the bank of the class
    probabilities of ``x``, one sample's nodes at a time."""
    bank = make_bank(cfg, seed, samples, nodes, spread, gain)
    # one node after another (lax.map), so that every convolution has one
    # set of weights: the TPU compiler failed on the grouped bfloat16
    # convolutions that a vmap over nodes makes
    per_sample = jax.jit(lambda th, xx: jnp.sum(jax.lax.map(
        lambda t: probs(t, xx, dtype).astype(jnp.float32), th), axis=0))
    total = jnp.zeros((x.shape[0], cfg["num_classes"]), jnp.float32)
    for s in range(samples):
        total = total + per_sample(jax.tree.map(lambda a: a[s], bank), x)
    del bank
    return total / (samples * nodes)
