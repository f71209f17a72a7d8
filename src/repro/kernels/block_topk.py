"""Block-local top-k selection for the Pallas pack kernels (the paper's Q).

The compression hot-spot of CD-BFL: Q(θ - v) over p params every round
(p = 2.7M for the radar model, up to 314B for grok-1 — per-shard on the
mesh). Exact global top-k needs a global sort (host-hostile on TPU); the
TPU-native adaptation selects the top-k *within each VMEM block* via
**threshold bisection** — vector compares + reductions only, no sort, fully
MXU/VPU friendly:

    P(τ) = count(|x| >= τ) >= k   is monotone in τ;
    40 float32 bisection steps isolate the k-th magnitude per block.

Mosaic has no ``cumsum``, so the index-order rank that breaks ties at the
threshold is a matmul against a triangular 0/1 matrix
(:func:`_row_prefix_count`), exact for any block size.

These are in-kernel helpers of the pack kernel (``pack.py``) and the
fused delta-pack kernel (``fused_compress.py``). Layout: input reshaped to
(num_blocks, block_size); one grid row processes ``ROWS_PER_TILE`` blocks;
block_size is a multiple of 128 (lane width).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROWS_PER_TILE = 8
BISECT_ITERS = 40
LANES = 128


def _bisect_threshold(mag, k: int):
    """Per-row ``(lo, hi)`` with count(mag >= lo) >= k > count(mag >= hi)."""
    hi = jnp.max(mag, axis=1, keepdims=True) + 1.0     # P(hi) = False
    lo = jnp.zeros_like(hi)                            # P(lo) = True

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((mag >= mid).astype(jnp.float32), axis=1, keepdims=True)
        pred = cnt >= k
        return jnp.where(pred, mid, lo), jnp.where(pred, hi, mid)

    return jax.lax.fori_loop(0, BISECT_ITERS, body, (lo, hi))


def _row_prefix_count(mask):
    """Inclusive prefix count of a ``(rows, bs)`` bool mask along each row.

    Each 128-lane chunk is multiplied by an upper-triangular 0/1 matrix on
    the MXU and the chunk totals carry into the next chunk. Operands are
    0/1 (exact in bf16) and the f32 accumulator sums at most 128 ones, so
    the counts are exact integers.
    """
    rows, bs = mask.shape
    r = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    tri = (r <= c).astype(jnp.float32).astype(jnp.bfloat16)
    m = mask.astype(jnp.float32).astype(jnp.bfloat16)
    carry = jnp.zeros((rows, 1), jnp.float32)
    parts = []
    for s in range(0, bs, LANES):
        w = min(LANES, bs - s)
        pre = jnp.dot(m[:, s:s + w], tri[:w, :w],
                      preferred_element_type=jnp.float32) + carry
        parts.append(pre)
        carry = pre[:, w - 1:w]
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return out.astype(jnp.int32)
