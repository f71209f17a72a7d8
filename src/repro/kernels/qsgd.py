"""Pallas TPU kernel: QSGD stochastic quantization (Alistarh et al. '17).

    q(x) = sign(x) · ⌊ |x|/‖x‖ · s + u ⌋ · ‖x‖/s,   u ~ U[0,1)

Used as the alternative compression operator Q for CD-BFL (paper cites QSGD
as [26]). The per-leaf 2-norm (eps included) is a reduction computed by the
jit wrapper (ops.py) and passed as a (1,1) scalar operand; the kernel is the
memory-bound elementwise pass with stochastic rounding. Uniform randoms are
an input stream (TPU variant: pltpu.prng_random_bits per tile).

The rounding rule and association order match ``_qsgd_leaf`` in
``core/compression.py`` **bitwise** — ``lower + (u < prob)`` rather than
``floor(scaled + u)`` (same distribution, different bits for the same u),
and ``sign·q·norm/levels/(1+ω)`` evaluated left to right — so the kernel,
the codec stage, and the fused-compress grid-quant kernel are
cross-checked against each other in tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_R = 256
TILE_C = 128


def _qsgd_kernel(x_ref, u_ref, norm_ref, o_ref, *, levels: int,
                 omega: float = 0.0):
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    norm = norm_ref[0, 0]
    scaled = jnp.abs(x) / norm * levels
    lower = jnp.floor(scaled)
    q = lower + (u < scaled - lower).astype(jnp.float32)
    # 1/(1+omega) scaling makes the operator a delta-contraction (CHOCO req.)
    o_ref[...] = (jnp.sign(x) * q * norm / levels / (1.0 + omega)).astype(
        o_ref.dtype)


def qsgd_pallas(x, uniform, norm, levels: int, *, omega: float = 0.0,
                interpret: bool):
    """x/uniform (R, C); norm (1,1) float32."""
    r, c = x.shape
    assert r % TILE_R == 0 and c == TILE_C, (r, c)
    grid = (r // TILE_R,)
    spec = pl.BlockSpec((TILE_R, TILE_C), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_qsgd_kernel, levels=levels, omega=omega),
        grid=grid,
        in_specs=[spec, spec,
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r, c), x.dtype),
        interpret=interpret,
    )(x, uniform, norm)
