"""Dense-masked reference operators for the codec pipelines.

Each takes a leaf and its key and returns Q(x) as a tensor of the leaf's
shape and dtype, with the dropped entries zeroed. ``decode(encode(x))`` of
the matching single-stage pipeline in ``repro.core.compression`` is
checked against these bit for bit (tests/test_wire.py).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import _qsgd_omega, _randk_indices


def topk(x, key, *, ratio: float):
    """Exact global top-|.| sparsification of a leaf (reference semantics).

    Selection goes through ``top_k`` *indices* (ties broken deterministically
    toward the lower index) rather than a ``mag >= thresh`` mask, so exactly
    ``k`` entries survive even with tied magnitudes — the sparsity budget the
    wire accounting assumes is never exceeded.
    """
    flat = x.reshape(-1)
    n = flat.shape[0]
    k = max(1, int(np.ceil(ratio * n)))
    if k >= n:
        return x
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    out = jnp.zeros_like(flat).at[idx].set(flat[idx])
    return out.reshape(x.shape)


def block_topk(x, key, *, ratio: float, block_size: int):
    """Block-local top-k: each contiguous block keeps its own top entries.

    Same sparsity budget as global top-k but the selection is local to a
    block (VMEM-tile computable on TPU). Pads the tail block with zeros.
    """
    flat = x.reshape(-1)
    n = flat.shape[0]
    if n <= block_size:
        return topk(x, key, ratio=ratio)
    nb = -(-n // block_size)
    padded = jnp.pad(flat, (0, nb * block_size - n))
    blocks = padded.reshape(nb, block_size)
    k = max(1, int(np.ceil(ratio * block_size)))
    # index-based selection: exactly k per block, ties -> lower index
    _, idx = jax.lax.top_k(jnp.abs(blocks), k)
    vals = jnp.take_along_axis(blocks, idx, axis=1)
    out = jnp.zeros_like(blocks).at[jnp.arange(nb)[:, None], idx].set(vals)
    return out.reshape(-1)[:n].reshape(x.shape)


def randk(x, key, *, ratio: float):
    """Biased (CHOCO-style) rand-k: keep exactly k = ceil(ratio·n) random
    coordinates, NO 1/ratio rescale.

    The unbiased ``mask/ratio`` variant violates the module's contraction
    contract: E||Q(x)-x||² = (1/ratio − 1)||x||², which exceeds
    (1 − ratio)||x||² for ratio < 0.618 — CHOCO error feedback requires the
    biased form. With exactly k coordinates the contraction is deterministic:
    ||Q(x)-x||² = (1 − k/n)||x||² in expectation over the uniform index set.
    """
    flat = x.reshape(-1)
    n = flat.shape[0]
    k = max(1, int(np.ceil(ratio * n)))
    if k >= n:
        return x
    idx = _randk_indices(key, n, k)
    out = jnp.zeros_like(flat).at[idx].set(flat[idx])
    return out.reshape(x.shape)


def sign(x, key):
    """1-bit sign compression scaled by mean magnitude (SignSGD w/ norm)."""
    scale = jnp.mean(jnp.abs(x))
    return jnp.sign(x) * scale


def qsgd(x, key, *, levels: int):
    """QSGD stochastic quantization (Alistarh et al. '17), per-leaf norm.

    Scaled by 1/(1+omega) so the operator is a delta-contraction with
    delta = 1/(1+omega) — the form CHOCO-style error feedback requires
    (an *unbiased* high-variance q would break the control sequences).
    """
    norm = jnp.linalg.norm(x.reshape(-1).astype(jnp.float32)) + 1e-12
    scaled = jnp.abs(x.astype(jnp.float32)) / norm * levels
    lower = jnp.floor(scaled)
    prob = scaled - lower
    rnd = jax.random.uniform(key, x.shape)
    q = lower + (rnd < prob).astype(jnp.float32)
    omega = _qsgd_omega(x.size, levels)
    out = jnp.sign(x) * q * norm / levels / (1.0 + omega)
    return out.astype(x.dtype)

