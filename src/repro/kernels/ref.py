"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def block_topk_ref(x2d: jnp.ndarray, k: int) -> jnp.ndarray:
    """Exact per-row top-k by magnitude (index-based, exactly k survive
    even under ties — the jax.lax.top_k rule)."""
    mag = jnp.abs(x2d.astype(jnp.float32))
    _, idx = jax.lax.top_k(mag, k)
    vals = jnp.take_along_axis(x2d, idx, axis=1)
    rows = jnp.arange(x2d.shape[0])[:, None]
    return jnp.zeros_like(x2d).at[rows, idx].set(vals)


def block_topk_bisect_ref(x2d: jnp.ndarray, k: int, iters: int = 40
                          ) -> jnp.ndarray:
    """Bisection semantics — bit-exact oracle of the kernel's algorithm."""
    mag = jnp.abs(x2d.astype(jnp.float32))
    hi = jnp.max(mag, axis=1, keepdims=True) + 1.0
    lo = jnp.zeros_like(hi)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((mag >= mid).astype(jnp.float32), axis=1, keepdims=True)
        pred = cnt >= k
        return jnp.where(pred, mid, lo), jnp.where(pred, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    # same exact-k tie rule as the kernel: definite survivors (> threshold)
    # plus tied-at-threshold entries in index order up to k
    mask_def = mag >= hi
    mask_tie = (mag >= lo) & ~mask_def
    n_def = jnp.sum(mask_def.astype(jnp.int32), axis=1, keepdims=True)
    pos_tie = n_def + jnp.cumsum(mask_tie.astype(jnp.int32), axis=1) - 1
    mask = mask_def | (mask_tie & (pos_tie < k))
    return jnp.where(mask, x2d, jnp.zeros_like(x2d))


def fused_update_ref(theta, vbar, v, noise, zeta: float, noise_scale: float):
    out = (theta.astype(jnp.float32)
           + zeta * (vbar.astype(jnp.float32) - v.astype(jnp.float32))
           + noise_scale * noise.astype(jnp.float32))
    return out.astype(theta.dtype)
