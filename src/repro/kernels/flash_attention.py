"""Causal GQA flash attention as fused Pallas TPU kernels, forward and
backward.

``flash_gqa`` takes causal full attention in two kernels. The forward runs
the online softmax and saves each row's log-sum-exp; the backward
recomputes each block's probabilities from it and takes dQ, dK and dV in
one pass. Every block's scores, running max and running sum stay in VMEM
in float32; no ``S x S`` score or probability array reaches HBM. Key
blocks that the causal mask hides entirely are skipped, their copies too
(the block index maps repeat the last block needed), and only the blocks
on the diagonal build the mask.

GQA: the ``G = H / KV`` query heads of a KV head are one block of
``G * blk`` rows against that head's keys and values, so each key block is
read once per query block. Products take the inputs' dtype (bfloat16 in
training) with float32 accumulation, the probabilities enter the PV and
dV products in that dtype, and the softmax statistics are float32. ``q``
is scaled by ``1/sqrt(hd)`` before the kernels.

One block size serves queries and keys, forward and backward: the largest
of ``BLOCKS`` that divides the sequence (:func:`flash_block`). On a TPU
v5e, for 16 sequences of 2,048 tokens, hd 64 and 3 query heads a KV
head, two forwards and a backward (a recomputed training step) took 9.18
ms at 512, 9.71 ms at 1,024 (its backward 20% slower) and 11.21 ms at
256. The backward keeps the whole sequence's dQ of a batch row and KV head in
VMEM, in float32, which bounds ``G * S * hd`` (:func:`supported`).

The pallas calls carry no kernel metadata, so each custom call prints on
one line of the compiled HLO text, with the caller's named scope
(``attention``) in its ``op_name``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

BLOCKS = (512, 256, 128)
HEAD_DIMS = (64, 128)
LANES = 128
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
NT = (((1,), (1,)), ((), ()))            # a @ b.T
TN = (((0,), (0,)), ((), ()))            # a.T @ b
VMEM_LIMIT = 64 * 1024 * 1024
DQ_VMEM = 8 * 1024 * 1024                # the backward's float32 dQ


def flash_block(s: int) -> Optional[int]:
    """The query and key block for sequence length ``s``, or None when no
    block of ``BLOCKS`` divides it."""
    return next((b for b in BLOCKS if s % b == 0), None)


def supported(s: int, hd: int, g: int) -> bool:
    """True when the kernels take sequence length ``s``, head size ``hd``
    and ``g`` query heads a KV head."""
    return (flash_block(s) is not None and hd in HEAD_DIMS
            and 4 * g * s * hd <= DQ_VMEM)


def _lanes(x, n):
    """(rows, 128) lane-replicated column -> (rows, n)."""
    if n <= LANES:
        return x[:, :n]
    return jnp.tile(x, (1, n // LANES))


def _causal(s, blk, rows_axis):
    """A diagonal block's scores with the keys after each query set to
    ``MASK_VALUE``. Queries run along ``rows_axis``, heads-major in blocks
    of ``blk``; keys along the other axis."""
    q = lax.broadcasted_iota(jnp.int32, s.shape, rows_axis) % blk
    k = lax.broadcasted_iota(jnp.int32, s.shape, 1 - rows_axis)
    return jnp.where(k <= q, s, MASK_VALUE)


def _on_blocks(i, j, step):
    """``step(masked)`` on query block ``i`` and key block ``j``: whole
    below the diagonal, masked on it, skipped above it."""
    pl.when(j < i)(lambda: step(False))
    pl.when(j == i)(lambda: step(True))


def _rows(ref):
    """A (G, blk, hd) block as (G * blk, hd), heads-major."""
    g, blk, hd = ref.shape
    return ref[...].reshape(g * blk, hd)


def _params(*sem):
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=VMEM_LIMIT)


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc):
    i, j = pl.program_id(1), pl.program_id(2)
    g, blk, hd = q_ref.shape

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, MASK_VALUE)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked):
        s = lax.dot_general(_rows(q_ref), k_ref[...], NT,
                            preferred_element_type=jnp.float32)
        if masked:
            s = _causal(s, blk, 0)
        m_prev = m_sc[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, blk))
        alpha = jnp.exp(m_prev - m_next)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_next
        v = v_ref[...]
        acc_sc[...] = _lanes(alpha, hd) * acc_sc[...] + lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    _on_blocks(i, j, step)

    @pl.when(j == i)
    def _end():
        l = l_sc[...]
        out = acc_sc[...] * _lanes(1.0 / l, hd)
        o_ref[...] = out.reshape(g, blk, hd).astype(o_ref.dtype)
        lse_ref[...] = (m_sc[...] + jnp.log(l)).T[:1]


def _fwd(q, k, v, blk, interpret):
    """q (N,G,S,hd), k/v (N,S,hd) -> out (N,G,S,hd) and the log-sum-exp
    (N, S/blk, 1, G*blk) f32, each query block's G heads in one row."""
    n, g, s, hd = q.shape
    nb = s // blk
    qo = pl.BlockSpec((None, g, blk, hd), lambda b, i, j: (b, 0, i, 0))
    kv = pl.BlockSpec((None, blk, hd),
                      lambda b, i, j: (b, jnp.minimum(j, i), 0))
    row = pl.BlockSpec((None, None, 1, g * blk), lambda b, i, j: (b, i, 0, 0))
    return pl.pallas_call(
        _fwd_kernel,
        grid=(n, nb, nb),
        in_specs=[qo, kv, kv],
        out_specs=[qo, row],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((n, nb, 1, g * blk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g * blk, LANES), jnp.float32),
                        pltpu.VMEM((g * blk, LANES), jnp.float32),
                        pltpu.VMEM((g * blk, hd), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


# --------------------------------------------------------------------------
# Backward: dQ, dK and dV in one pass
# --------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc):
    j, i = pl.program_id(1), pl.program_id(2)
    g, blk, hd = q_ref.shape

    @pl.when(jnp.logical_and(j == 0, i == 0))
    def _init_dq():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(i == 0)
    def _init_dkv():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(masked):
        q, do, k = _rows(q_ref), _rows(do_ref), k_ref[...]
        st = lax.dot_general(k, q, NT,
                             preferred_element_type=jnp.float32)  # (blk, G blk)
        if masked:
            st = _causal(st, blk, 1)
        pt = jnp.exp(st - lse_ref[...])
        dv_sc[...] += lax.dot(pt.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_ref[...], do, NT,
                              preferred_element_type=jnp.float32)
        dst = (pt * (dpt - di_ref[...])).astype(q.dtype)
        dk_sc[...] += lax.dot(dst, q, preferred_element_type=jnp.float32)
        dq = lax.dot_general(dst, k, TN, preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(i * blk, blk), blk)
        dq_sc[:, rows, :] += dq.reshape(g, blk, hd)

    _on_blocks(i, j, step)

    @pl.when(i == pl.num_programs(2) - 1)
    def _end_dkv():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(j == pl.num_programs(1) - 1,
                             i == pl.num_programs(2) - 1))
    def _end_dq():
        dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)


def _bwd(q, k, v, do, lse, di, blk, interpret):
    """Grid (batch row and KV head, key block j, query block i): dK and dV
    of block j sum over the query blocks and heads; dQ of the whole
    sequence stays in VMEM until the row is done."""
    n, g, s, hd = q.shape
    nb = s // blk
    qo = pl.BlockSpec((None, g, blk, hd),
                      lambda b, j, i: (b, 0, jnp.maximum(i, j), 0))
    row = pl.BlockSpec((None, None, 1, g * blk),
                       lambda b, j, i: (b, jnp.maximum(i, j), 0, 0))
    kv = pl.BlockSpec((None, blk, hd), lambda b, j, i: (b, j, 0))
    whole = pl.BlockSpec((None, g, s, hd), lambda b, j, i: (b, 0, 0, 0))
    return pl.pallas_call(
        _bwd_kernel,
        grid=(n, nb, nb),
        in_specs=[qo, kv, kv, qo, row, row],
        out_specs=[whole, kv, kv],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((g, s, hd), jnp.float32),
                        pltpu.VMEM((blk, hd), jnp.float32),
                        pltpu.VMEM((blk, hd), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name="flash_attention_bwd",
    )(q, k, v, do, lse, di)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, blk, interpret):
    return _fwd(q, k, v, blk, interpret)[0]


def _flash_fwd(q, k, v, blk, interpret):
    out, lse = _fwd(q, k, v, blk, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(blk, interpret, res, do):
    q, k, v, out, lse = res
    n, g, s, _ = q.shape
    di = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = di.reshape(n, g, s // blk, blk).transpose(0, 2, 1, 3).reshape(
        lse.shape)                       # laid out as the log-sum-exp
    return _bwd(q, k, v, do, lse, di, blk, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_gqa(q, k, v, *, block: Optional[int] = None):
    """q (B,S,H,hd), k/v (B,S,KV,hd) -> (B,S,H,hd), causal.

    Compiled by Mosaic on a TPU, interpreted elsewhere
    (:func:`repro.kernels.interpret_mode`). ``block`` defaults to
    :func:`flash_block`; a smaller one lets a test cover several blocks at
    a small ``S``."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    assert supported(s, hd, g), (s, hd, g)
    block = block or flash_block(s)
    assert s % block == 0 and block % LANES == 0, (s, block)
    qg = (q.astype(jnp.float32) * hd ** -0.5).astype(q.dtype)
    qg = qg.reshape(b, s, kvh, g, hd).transpose(0, 2, 3, 1, 4)
    kg = k.transpose(0, 2, 1, 3).reshape(b * kvh, s, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(b * kvh, s, hd)
    out = _flash(qg.reshape(b * kvh, g, s, hd), kg, vg, block,
                 interpret_mode())
    return out.reshape(b, kvh, g, s, hd).transpose(0, 3, 1, 2, 4).reshape(
        b, s, h, hd)
