"""The one traffic generator: inputs, weights and arrivals from ``--seed``.

Every function takes the seed and the sizes a configuration or a traffic
file states, and nothing of the system under test. Arrays are made on the
device in one jitted call each; the same seed gives the same bits.
"""
from __future__ import annotations

from functools import lru_cache

import numpy as np

from bench.common import seed_bits

# one salt per stream, so that adding a stream moves no other
SALT_INIT, SALT_DATA, SALT_STATE, SALT_KEY, SALT_BANK, SALT_FRAMES, \
    SALT_ARRIVALS, SALT_SAMPLE = range(1, 9)


def key(seed: int, salt: int):
    import jax
    return jax.random.PRNGKey(seed_bits(seed, salt))


@lru_cache(maxsize=None)
def _radar_fn(n: int, h: int, w: int, classes: int):
    import jax
    import jax.numpy as jnp

    def make(k):
        """Range-azimuth maps: one reflector whose range and azimuth follow
        the class (its region of interest), over Rayleigh-like clutter."""
        ky, kr, ka, kc, kg = jax.random.split(k, 5)
        y = jax.random.randint(ky, (n,), 0, classes, jnp.int32)
        r0 = (y.astype(jnp.float32) + 0.5) / classes * h
        a0 = (0.25 + 0.5 * (y % 2).astype(jnp.float32)) * w
        r0 = r0 + jax.random.normal(kr, (n,)) * 0.05 * h / classes * 4
        a0 = a0 + jax.random.normal(ka, (n,)) * 0.05 * w
        rr = jnp.arange(h, dtype=jnp.float32)[None, :, None]
        aa = jnp.arange(w, dtype=jnp.float32)[None, None, :]
        blob = jnp.exp(-((rr - r0[:, None, None]) / (0.02 * h + 1)) ** 2
                       - ((aa - a0[:, None, None]) / (0.05 * w + 1)) ** 2)
        clutter = jnp.abs(jax.random.normal(kc, (n, h, w))
                          + 1j * jax.random.normal(kg, (n, h, w))) * 0.2
        x = (blob + clutter)[..., None].astype(jnp.float32)
        return x, y

    return jax.jit(make)


def radar_maps(seed: int, salt: int, n: int, hw, classes: int):
    """``(n, H, W, 1)`` float32 maps and ``(n,)`` int32 labels, on device."""
    return _radar_fn(int(n), int(hw[0]), int(hw[1]), int(classes))(
        key(seed, salt))


def radar_pool(seed: int, nodes: int, per_node: int, hw, classes: int):
    """Per-node training pools: ``x (K, N, H, W, 1)``, ``y (K, N)``."""
    x, y = radar_maps(seed, SALT_DATA, nodes * per_node, hw, classes)
    return (x.reshape((nodes, per_node) + x.shape[1:]),
            y.reshape(nodes, per_node))


def poisson_arrivals(seed: int, rate: float, seconds: float, pool: int):
    """Open-loop arrivals: due times (s from the window's start) of a
    Poisson process at ``rate`` per second, and the frame each request
    sends. The count is fixed by the rate and the length alone (the gaps
    are rescaled to fill the window), so every seed offers the same work."""
    rng = np.random.default_rng(seed_bits(seed, SALT_ARRIVALS))
    n = max(1, int(round(rate * seconds)))
    gaps = rng.exponential(1.0, n)
    due = np.cumsum(gaps)
    due = due / due[-1] * seconds * (n - 0.5) / n
    frames = rng.integers(0, pool, n)
    return due, frames
