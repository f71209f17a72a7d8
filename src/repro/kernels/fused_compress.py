"""Pallas TPU kernels: compress-in-update — the residual never hits HBM.

The two-pass encode path of CD-BFL (DESIGN.md §2) materializes the dense
residual ``delta = theta - v`` (one full write of p floats), then re-reads
it to threshold, pack, and quantize: ~5p floats of HBM traffic before a
single wire byte exists. At transformer scale that traffic dominates the
round (ROADMAP item 5). This module fuses the pipeline into the update
read itself, one kernel per stage-pair of the ``"block_topk|qsgd"`` DSL:

* **delta_pack** (`sparsify` stage 0): reads a ``theta`` tile and a ``v``
  tile, forms ``d = theta - v.astype(theta.dtype)`` *in VMEM*, and runs
  the exact ``pack.py`` bisection / two-tier prefix-rank compaction
  (shared :func:`~repro.kernels.pack._pack_tile` body) on it. The dense
  residual exists only as a (ROWS_PER_TILE, block_size) register tile;
  HBM sees ``2p`` reads (theta + v) and wire-sized writes.
* **grid_quant** (`quantize` stage 1): stochastic QSGD rounding of the
  *packed carrier* onto the signed integer grid, bit-for-bit the
  arithmetic of ``QSGDCodec.encode`` (same ``lower + (u < prob)``
  rounding, same association order). The per-leaf 2-norm is a global
  reduction over the wire-sized carrier, so it is computed between the
  two kernels by the ``ops.py`` wrapper — the one unavoidable stage
  boundary, at O(wire) not O(p) cost.

Eligibility and fallback semantics live in ``core/compression.py``
(:class:`FusedCodec`); the two-pass path is kept verbatim as the bitwise
reference oracle behind ``fused=False``. Layout conventions follow
``pack.py`` (f32 tiles of ``ROWS_PER_TILE`` blocks, slots padded to a lane
multiple inside the kernel and sliced back to ``k`` by the wrapper).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pack import (ROWS_PER_TILE, _lane_pad, _pack_tile,
                                _row_tiled_call)


def _delta_pack_kernel(t_ref, v_ref, vals_ref, idx_ref, *, k: int):
    t = t_ref[...]                                     # (rows, bs)
    # the residual lives only in this tile — same arithmetic as the round
    # functions' `t - v.astype(t.dtype)` (v may ride at control_dtype)
    d = t - v_ref[...].astype(t.dtype)
    vals, idx = _pack_tile(d, k=k)
    vals_ref[...] = vals.astype(vals_ref.dtype)
    idx_ref[...] = idx


def delta_pack_pallas(t2d: jnp.ndarray, v2d: jnp.ndarray, k: int, *,
                      interpret: bool):
    """(theta, v) as (num_blocks, block_size) -> (vals (nb, k), idx i32)."""
    nb, bs = t2d.shape
    assert v2d.shape == (nb, bs), (t2d.shape, v2d.shape)
    kp = _lane_pad(k)
    vals, idx = _row_tiled_call(
        functools.partial(_delta_pack_kernel, k=k), nb, [bs, bs],
        [jax.ShapeDtypeStruct((nb, kp), t2d.dtype),
         jax.ShapeDtypeStruct((nb, kp), jnp.int32)], interpret)(t2d, v2d)
    return vals[:, :k], idx[:, :k]


def _grid_quant_kernel(x_ref, u_ref, norm_ref, q_ref, *, levels: int):
    f = x_ref[...].astype(jnp.float32)
    norm = norm_ref[0, 0]                 # ||carrier|| + eps, from wrapper
    scaled = jnp.abs(f) / norm * levels
    lower = jnp.floor(scaled)
    q = lower + (u_ref[...] < scaled - lower).astype(jnp.float32)
    q_ref[...] = (jnp.sign(f) * q).astype(q_ref.dtype)


def grid_quant_pallas(x: jnp.ndarray, uniform: jnp.ndarray,
                      norm: jnp.ndarray, levels: int, out_dtype, *,
                      interpret: bool) -> jnp.ndarray:
    """Quantize a packed (rows, k) carrier onto the signed QSGD grid.

    Emits the integer carrier ``sign(x)·q`` that crosses the wire
    (``QSGDCodec._wire_dtype()``); the f32 reconstruction happens at
    decode. ``norm`` is the (1, 1) f32 carrier norm (eps included).
    """
    r, k = x.shape
    assert r % ROWS_PER_TILE == 0, f"pad rows to {ROWS_PER_TILE}"
    grid = (r // ROWS_PER_TILE,)
    spec = pl.BlockSpec((ROWS_PER_TILE, k), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_grid_quant_kernel, levels=levels),
        grid=grid,
        in_specs=[spec, spec, pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r, k), out_dtype),
        interpret=interpret,
    )(x, uniform, norm)
