"""Jit'd public wrappers around the Pallas kernels.

Handle flattening/padding of arbitrary param leaves into the kernels' tiled
2D layouts, and expose pytree-level entry points. Every wrapper runs its
kernel in the mode :func:`repro.kernels.interpret_mode` picks from the
platform: compiled on a TPU, interpreted elsewhere.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.block_topk import ROWS_PER_TILE
from repro.kernels.fused_compress import delta_pack_pallas, grid_quant_pallas
from repro.kernels.fused_update import TILE_C, TILE_R, fused_update_pallas
from repro.kernels.pack import pack_topk_pallas, unpack_topk_pallas


def _pad_to_2d(x: jnp.ndarray, cols: int, row_mult: int
               ) -> Tuple[jnp.ndarray, int]:
    """Flatten to (rows, cols), zero-padded; returns (x2d, orig_size)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = -(-n // cols)
    rows = -(-rows // row_mult) * row_mult
    padded = jnp.zeros((rows * cols,), flat.dtype).at[:n].set(flat)
    return padded.reshape(rows, cols), n


def _unpad(x2d: jnp.ndarray, n: int, shape) -> jnp.ndarray:
    return x2d.reshape(-1)[:n].reshape(shape)


# --------------------------------------------------------------------------
# block top-k wire format: tile-local pack / unpack (DESIGN.md §2)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("ratio", "block_size"))
def block_topk_pack(x: jnp.ndarray, ratio: float = 0.01,
                    block_size: int = 1024):
    """Pack a leaf into the wire format: (vals (nb, k), idx uint16).

    ``nb = ceil(x.size / block_size)`` — the all-zero rows the kernel adds
    to reach the tile multiple are sliced off, so the payload (and its
    measured bytes) covers only real blocks. ``idx`` is block-local so
    uint16 suffices for block_size <= 65536. The original element count is
    ``x.size`` (static at the call site).
    """
    assert block_size <= 65536, "uint16 block-local indices"
    k = max(1, int(np.ceil(ratio * block_size)))
    nb = max(1, -(-x.size // block_size))
    x2d, _ = _pad_to_2d(x, block_size, ROWS_PER_TILE)
    vals, idx = pack_topk_pallas(x2d, k, interpret=interpret_mode())
    return vals[:nb], idx[:nb].astype(jnp.uint16)


@functools.partial(jax.jit, static_argnames=("n", "shape", "block_size"))
def block_topk_unpack(vals: jnp.ndarray, idx: jnp.ndarray, n: int, shape,
                      block_size: int = 1024):
    """Scatter a packed (vals, idx) payload back to the dense masked leaf.

    Re-pads the block rows to the kernel's tile multiple (zero vals at
    index 0 — harmless: the pad rows are dropped by the final [:n] slice).
    """
    nb = vals.shape[0]
    nb_pad = -(-nb // ROWS_PER_TILE) * ROWS_PER_TILE
    vals = jnp.pad(vals, ((0, nb_pad - nb), (0, 0)))
    idx = jnp.pad(idx.astype(jnp.int32), ((0, nb_pad - nb), (0, 0)))
    dense2d = unpack_topk_pallas(vals, idx, block_size,
                                 interpret=interpret_mode())
    return _unpad(dense2d, n, shape)


# --------------------------------------------------------------------------
# fused Eq. 9 update
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("zeta", "noise_scale"))
def fused_update(theta, vbar, v, noise, zeta: float, noise_scale: float):
    if theta.size == 0:      # zero-size leaf: a (0,)-grid pallas_call is
        return theta         # ill-formed, and the update is vacuous anyway
    t2, n = _pad_to_2d(theta, TILE_C, TILE_R)
    vb2, _ = _pad_to_2d(vbar, TILE_C, TILE_R)
    v2, _ = _pad_to_2d(v, TILE_C, TILE_R)
    n2, _ = _pad_to_2d(noise, TILE_C, TILE_R)
    out = fused_update_pallas(t2, vb2, v2, n2, zeta, noise_scale,
                              interpret=interpret_mode())
    return _unpad(out, n, theta.shape)


# --------------------------------------------------------------------------
# fused compress-in-update (DESIGN.md §13): delta never materializes
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("ratio", "block_size"))
def fused_delta_pack(theta: jnp.ndarray, v: jnp.ndarray, ratio: float = 0.01,
                     block_size: int = 1024):
    """``block_topk_pack(theta - v.astype(theta.dtype))`` without ever
    writing the dense residual (or a padded copy of it) to HBM.

    The leaf is split at the largest multiple of the kernel tile
    (``ROWS_PER_TILE * block_size`` elements): the aligned prefix is a
    pure reshape of ``theta``/``v`` — no copy, the kernel's two reads are
    the only O(p) traffic — and only the ragged tail (< one tile) is
    zero-padded, an O(tile) cost. Blocks are independent and the split
    point is a block boundary, so the result is bitwise-identical to the
    two-pass path, which pads the whole leaf via ``_pad_to_2d``.
    """
    assert block_size <= 65536, "uint16 block-local indices"
    k = max(1, int(np.ceil(ratio * block_size)))
    n = theta.size
    nb = max(1, -(-n // block_size))
    tile = ROWS_PER_TILE * block_size
    tf, vf = theta.reshape(-1), v.reshape(-1)
    n_head = (n // tile) * tile
    parts = []
    if n_head:
        parts.append(delta_pack_pallas(
            tf[:n_head].reshape(-1, block_size),
            vf[:n_head].reshape(-1, block_size), k,
            interpret=interpret_mode()))
    if n_head < n or not parts:
        tpad = jnp.zeros((tile,), tf.dtype).at[:n - n_head].set(tf[n_head:])
        vpad = jnp.zeros((tile,), vf.dtype).at[:n - n_head].set(vf[n_head:])
        parts.append(delta_pack_pallas(
            tpad.reshape(ROWS_PER_TILE, block_size),
            vpad.reshape(ROWS_PER_TILE, block_size), k,
            interpret=interpret_mode()))
    vals = jnp.concatenate([p[0] for p in parts])[:nb]
    idx = jnp.concatenate([p[1] for p in parts])[:nb].astype(jnp.uint16)
    return vals, idx


@functools.partial(jax.jit, static_argnames=("levels", "out_dtype"))
def qsgd_quantize_carrier(x: jnp.ndarray, key, levels: int = 16,
                          out_dtype=jnp.int8):
    """QSGD-quantize a packed ``(nb, k)`` carrier onto the signed integer
    wire grid: returns ``(grid (nb, k) out_dtype, norm () f32)``.

    Bitwise-identical to ``QSGDCodec.encode``'s carrier/scale pair: the
    eps-included norm, the uniforms drawn at ``x.shape`` with ``key``, and
    the grid arithmetic all match the codec. O(wire) traffic only.
    """
    nb, k = x.shape
    norm = jnp.linalg.norm(x.astype(jnp.float32).reshape(-1)) + 1e-12
    u = jax.random.uniform(key, x.shape)
    nb_pad = -(-nb // ROWS_PER_TILE) * ROWS_PER_TILE
    xp = jnp.pad(x, ((0, nb_pad - nb), (0, 0)))
    up = jnp.pad(u, ((0, nb_pad - nb), (0, 0)))
    grid = grid_quant_pallas(xp, up, norm.reshape(1, 1), levels, out_dtype,
                             interpret=interpret_mode())
    return grid[:nb], norm


# --------------------------------------------------------------------------
# pytree-level entry points
# --------------------------------------------------------------------------

def tree_fused_update(theta_tree, vbar_tree, v_tree, noise_tree,
                      zeta: float, noise_scale: float):
    return jax.tree.map(
        lambda t, vb, v, n: fused_update(t, vb, v, n, zeta=zeta,
                                         noise_scale=noise_scale),
        theta_tree, vbar_tree, v_tree, noise_tree)
