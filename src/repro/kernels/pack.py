"""Pallas TPU kernels: tile-local pack/unpack of block-top-k survivors.

The wire format of CD-BFL (DESIGN.md §2) ships, per block, a compacted
``(nb, k)`` value buffer plus block-local indices — not the dense masked
tensor the compute path keeps on device. These kernels materialize that
format tile-locally, with no sort and no data-dependent shapes:

* **pack**: per block, threshold bisection (as in ``block_topk.py``)
  isolates the k-th magnitude; each survivor's slot is its rank from a
  triangular-matmul prefix count. The ranking is two-tier: entries
  strictly above the threshold pack first (they can never be evicted),
  then ties at the threshold fill the remaining slots in index order —
  the same selection as ``jax.lax.top_k``, so exactly ``k`` survivors
  are packed per block. Slot ``s`` is then gathered by a masked lane
  reduction ``vals[r, s] = Σ_b x[r, b] · 1[pos[r, b] == s]``: one nonzero
  term, so values and indices come out exact.
* **unpack**: the inverse scatter, one masked select-add per slot,
  ``out[r, b] = Σ_s vals[r, s] · 1[idx[r, s] == b]``.

Layout: input reshaped to ``(num_blocks, block_size)``; one grid row
processes ``ROWS_PER_TILE`` blocks; ``block_size`` is a multiple of the
128-lane width. Inside the kernels the slot axis is padded to a lane
multiple ``kp`` so every store is lane-dense; the ``*_pallas`` wrappers
slice the ``(nb, kp)`` outputs back to ``k`` (and pad unpack's inputs).
Indices are emitted as int32 and narrowed to uint16 by the ``ops.py``
wrapper (block-local, so ``block_size <= 65536`` suffices).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.block_topk import (LANES, ROWS_PER_TILE, _bisect_threshold,
                                      _row_prefix_count)


def _lane_pad(k: int) -> int:
    return -(-k // LANES) * LANES


def _pack_tile(x, *, k: int):
    """Tile-local pack of one ``(rows, bs)`` block batch.

    Shared by :func:`_pack_kernel` and the fused delta-pack kernel in
    ``fused_compress.py`` — both paths run this exact arithmetic, so the
    fused encode is bitwise-identical to pack-after-materialize by
    construction. Returns ``(vals_f32, idx_i32)`` of shape ``(rows, kp)``
    before the output cast; slots ``k..kp-1`` are zero.
    """
    rows, bs = x.shape
    xf = x.astype(jnp.float32)
    mag = jnp.abs(xf)
    lo, hi = _bisect_threshold(mag, k)
    # Bisection invariants: count(mag >= lo) >= k, count(mag >= hi) < k.
    # Two-tier ranking so ties at the threshold cannot evict a definite
    # survivor: the < k entries strictly above the threshold (mag >= hi)
    # pack first, then the tied-at-threshold group fills the remaining
    # slots in index order — the same selection as jax.lax.top_k.
    mask_def = mag >= hi                               # definite: < k/row
    mask_tie = (mag >= lo) & ~mask_def                 # tied at the k-th
    n_def = jnp.sum(mask_def.astype(jnp.int32), axis=1, keepdims=True)
    pos_def = _row_prefix_count(mask_def) - 1
    pos_tie = n_def + _row_prefix_count(mask_tie) - 1
    # non-survivors, and ties ranked past the k-th, match no slot < k
    pos = jnp.where(mask_def, pos_def, jnp.where(mask_tie, pos_tie, bs))
    cols = jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1
                                    ).astype(jnp.float32)
    slots = jax.lax.broadcasted_iota(jnp.int32, (rows, _lane_pad(k)), 1)

    def gather(s, carry):
        vals, idx = carry
        hit = pos == s
        v = jnp.sum(jnp.where(hit, xf, 0.0), axis=1, keepdims=True)
        i = jnp.sum(jnp.where(hit, cols, 0.0), axis=1, keepdims=True)
        return (jnp.where(slots == s, v, vals), jnp.where(slots == s, i, idx))

    zeros = jnp.zeros(slots.shape, jnp.float32)
    vals, idx = jax.lax.fori_loop(0, k, gather, (zeros, zeros))
    return vals, idx.astype(jnp.int32)


def _pack_kernel(x_ref, vals_ref, idx_ref, *, k: int):
    vals, idx = _pack_tile(x_ref[...], k=k)
    vals_ref[...] = vals.astype(vals_ref.dtype)
    idx_ref[...] = idx


def _unpack_kernel(vals_ref, idx_ref, o_ref, *, k: int):
    vals = vals_ref[...].astype(jnp.float32)           # (rows, kp)
    idx = idx_ref[...].astype(jnp.float32)             # exact: < 2**24
    rows, bs = o_ref.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1
                                    ).astype(jnp.float32)
    slots = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)

    def scatter(s, out):
        at = slots == s
        v = jnp.sum(jnp.where(at, vals, 0.0), axis=1, keepdims=True)
        i = jnp.sum(jnp.where(at, idx, 0.0), axis=1, keepdims=True)
        return out + jnp.where(cols == i, v, 0.0)

    out = jax.lax.fori_loop(0, k, scatter, jnp.zeros((rows, bs), jnp.float32))
    o_ref[...] = out.astype(o_ref.dtype)


def _row_tiled_call(kernel, nb: int, in_widths, out_shapes, interpret: bool):
    """``pallas_call`` over ``ROWS_PER_TILE``-row tiles of full-width rows."""
    assert nb % ROWS_PER_TILE == 0, f"pad num_blocks to {ROWS_PER_TILE}"

    def spec(w):
        return pl.BlockSpec((ROWS_PER_TILE, w), lambda i: (i, 0))

    return pl.pallas_call(
        kernel,
        grid=(nb // ROWS_PER_TILE,),
        in_specs=[spec(w) for w in in_widths],
        out_specs=[spec(s.shape[1]) for s in out_shapes],
        out_shape=list(out_shapes),
        interpret=interpret,
    )


def pack_topk_pallas(x2d: jnp.ndarray, k: int, *, interpret: bool):
    """x2d (num_blocks, block_size) -> (vals (nb, k), idx int32 (nb, k))."""
    nb, bs = x2d.shape
    kp = _lane_pad(k)
    vals, idx = _row_tiled_call(
        functools.partial(_pack_kernel, k=k), nb, [bs],
        [jax.ShapeDtypeStruct((nb, kp), x2d.dtype),
         jax.ShapeDtypeStruct((nb, kp), jnp.int32)], interpret)(x2d)
    return vals[:, :k], idx[:, :k]


def unpack_topk_pallas(vals: jnp.ndarray, idx: jnp.ndarray, block_size: int,
                       *, interpret: bool) -> jnp.ndarray:
    """(vals (nb, k), idx int32 (nb, k)) -> dense (nb, block_size)."""
    nb, k = vals.shape
    kp = _lane_pad(k)
    pad = ((0, 0), (0, kp - k))
    (dense,) = _row_tiled_call(
        functools.partial(_unpack_kernel, k=k), nb, [kp, kp],
        [jax.ShapeDtypeStruct((nb, block_size), vals.dtype)], interpret)(
        jnp.pad(vals, pad), jnp.pad(idx, pad))
    return dense
