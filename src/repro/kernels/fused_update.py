"""Pallas TPU kernel: fused CD-BFL consensus + Langevin update (paper Eq. 9).

    θ' = θ + ζ·(v̄ − v) + √(2η T)·ξ

Unfused this is 3 elementwise HLO ops = 4 reads + 3 writes of p floats; the
kernel does it in a single pass (4 reads + 1 write), a ~2× traffic cut on a
purely memory-bound op — this matters because CD-BFL runs it over every
parameter every round.

ξ is a standard-normal input stream here (CPU interpret has no pltpu PRNG);
on real TPU the documented variant seeds ``pltpu.prng_random_bits`` per tile
and converts via Box-Muller, dropping the noise read stream too (5 streams
-> 2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_R = 256
TILE_C = 128


def _fused_update_kernel(theta_ref, vbar_ref, v_ref, noise_ref, o_ref,
                         *, zeta: float, noise_scale: float):
    th = theta_ref[...].astype(jnp.float32)
    vb = vbar_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    xi = noise_ref[...].astype(jnp.float32)
    o_ref[...] = (th + zeta * (vb - v) + noise_scale * xi).astype(o_ref.dtype)


def fused_update_pallas(theta, vbar, v, noise, zeta: float, noise_scale: float,
                        *, interpret: bool):
    """All inputs (R, C) with R % TILE_R == 0 and C == TILE_C."""
    r, c = theta.shape
    assert r % TILE_R == 0 and c == TILE_C, (r, c)
    grid = (r // TILE_R,)
    spec = pl.BlockSpec((TILE_R, TILE_C), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_fused_update_kernel, zeta=zeta,
                          noise_scale=noise_scale),
        grid=grid,
        in_specs=[spec] * 4,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r, c), theta.dtype),
        interpret=interpret,
    )(theta, vbar, v, noise)
