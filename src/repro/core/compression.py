"""Compression codecs Q(.) for CD-BFL (paper Eq. 6) and their wire format.

All operators satisfy the standard delta-contraction contract used by the
CHOCO/Koloskova analysis the paper builds on:

    E ||Q(x) - x||^2  <=  (1 - delta) ||x||^2,   0 < delta <= 1

Q is a :class:`CompressionPipeline` (DESIGN.md §2): chainable
:class:`Codec` stages (``sparsify ∘ quantize``, e.g. the DSL string
``"block_topk|qsgd"``) with ``encode(tree, key) -> WirePayload`` and
``decode(payload) -> tree``. The :class:`WirePayload` *materializes* the
packed representation that actually crosses the link — per-block value
buffers, uint16 block-local indices, quantization scales — and computes
``measured_bytes()`` from the buffers themselves. Deltas compose
multiplicatively. :class:`FusedCodec` lowers a pipeline onto the Pallas
kernels (DESIGN.md §13).

TPU adaptation: exact *global* top-k needs a global sort — hostile to VMEM
tiling. ``block_topk`` keeps the top ``k_b`` entries of every aligned block
instead, computable tile-locally (the Pallas kernels of
``repro.kernels.pack`` and ``repro.kernels.fused_compress``) and satisfies
the same contraction bound with delta = ratio.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _randk_indices(key, n: int, k: int) -> jax.Array:
    """Exactly-k uniformly random coordinates, derived from ``key`` alone.

    Both endpoints of a link can regenerate the index set from the shared
    PRNG key, so rand-k payloads carry *values only* (plus the 8-byte key).
    """
    scores = jax.random.uniform(key, (n,))
    _, idx = jax.lax.top_k(scores, k)
    return idx


def _qsgd_omega(n: int, levels: int) -> float:
    """QSGD variance bound: E||q(x)-x||^2 <= omega ||x||^2 (Alistarh '17,
    Thm 3.2): omega = min(n/s^2, sqrt(n)/s)."""
    return float(min(n / levels ** 2, np.sqrt(n) / levels))


# ==========================================================================
# Codec pipeline layer: chainable stages with a materialized wire format
# ==========================================================================
#
# A pipeline is a chain of Codec stages. Stage 0 consumes the dense leaf;
# every later stage consumes the previous stage's *carrier* (the value
# buffer that would cross the link). Sparsifiers emit a packed carrier plus
# an index sidecar; quantizers re-encode the carrier at a narrower wire
# dtype plus a scale sidecar. decode() walks the stages in reverse.
#
# All shape arithmetic is static (python ints from leaf avals), so encode/
# decode trace cleanly under jit and WirePayload.measured_bytes() is a
# compile-time constant.


class _SparseMeta(NamedTuple):
    """Static decode info for topk/block_topk/randk stages."""
    shape: Tuple[int, ...]      # carrier shape consumed by the stage
    n: int                      # element count of that carrier
    k: int                      # survivors (per block for mode="block")
    mode: str                   # dense | global | block | pallas
    nb: int = 0                 # blocks (block/pallas modes)
    bs: int = 0                 # block size


class _QuantMeta(NamedTuple):
    """Static decode info for qsgd/sign stages."""
    shape: Tuple[int, ...]
    n: int
    in_dtype: str               # dtype of the carrier consumed
    levels: int = 0             # qsgd
    omega: float = 0.0          # qsgd contraction scaling


@dataclass(frozen=True)
class Codec:
    """One stage of a CompressionPipeline.

    ``encode(carrier, key) -> (carrier', aux, meta)`` where ``aux`` is the
    dict of sidecar buffers (indices / keys / scales) that ride along on the
    wire and ``meta`` is the static info ``decode(carrier', aux, meta)``
    needs to invert the stage. ``delta_for_n(n)`` is the stage contraction
    on a carrier of ``n`` elements; ``out_size(n)`` the carrier size it
    emits; ``sidecar_formula_bytes`` / ``carrier_formula_bytes`` the
    closed-form byte table kept as the cross-check for measured bytes.

    Purity: ``encode``/``decode`` are deterministic in their inputs; randomized stages thread an explicit key rather than ambient RNG.
    """

    name: str = "identity"
    kind: str = "identity"      # identity | sparsify | quantize

    def encode(self, x, key):
        raise NotImplementedError

    def decode(self, carrier, aux, meta):
        raise NotImplementedError

    def delta_for_n(self, n: int) -> float:
        return 1.0

    def out_size(self, n: int) -> int:
        return n

    def sidecar_formula_bytes(self, n: int) -> int:
        return 0

    def carrier_formula_bytes(self, n: int, elem_bytes: int = 4) -> int:
        return self.out_size(n) * elem_bytes


@dataclass(frozen=True)
class IdentityCodec(Codec):
    """No-op stage: ``decode(encode(x))`` is ``x`` bitwise, zero sidecar bytes."""
    name: str = "identity"
    kind: str = "identity"

    def encode(self, x, key):
        return x, {}, _SparseMeta(tuple(x.shape), int(np.prod(x.shape)),
                                  0, "dense")

    def decode(self, carrier, aux, meta):
        return carrier


def _scatter_flat(carrier, idx, meta):
    out = jnp.zeros((meta.n,), carrier.dtype).at[idx].set(carrier)
    return out.reshape(meta.shape)


@dataclass(frozen=True)
class TopKCodec(Codec):
    """Exact global top-|.|; packed carrier (k,) + 4-byte index sidecar."""

    name: str = "topk"
    kind: str = "sparsify"
    ratio: float = 0.01

    def encode(self, x, key):
        flat = x.reshape(-1)
        n = flat.shape[0]
        k = max(1, int(np.ceil(self.ratio * n)))
        if k >= n:
            return x, {}, _SparseMeta(tuple(x.shape), n, n, "dense")
        _, idx = jax.lax.top_k(jnp.abs(flat), k)
        vals = flat[idx]
        iw = jnp.uint16 if n <= np.iinfo(np.uint16).max else jnp.uint32
        return vals, {"idx": idx.astype(iw)}, _SparseMeta(
            tuple(x.shape), n, k, "global")

    def decode(self, carrier, aux, meta):
        if meta.mode == "dense":
            return carrier
        return _scatter_flat(carrier, aux["idx"].astype(jnp.int32), meta)

    def delta_for_n(self, n):
        return self.ratio

    def out_size(self, n):
        k = max(1, int(np.ceil(self.ratio * n)))
        return min(k, n)

    def sidecar_formula_bytes(self, n):
        if self.out_size(n) >= n:
            return 0
        iw = 2 if n <= np.iinfo(np.uint16).max else 4
        return self.out_size(n) * iw


@dataclass(frozen=True)
class BlockTopKCodec(Codec):
    """Block-local top-k; uint16 block-local indices, (nb, k) value buffer.

    ``use_pallas=True`` routes pack/unpack through the tile-local Pallas
    kernels (``repro.kernels.pack``, compiled on a TPU); the jnp path
    emits the survivors in ``top_k`` slot order.
    """

    name: str = "block_topk"
    kind: str = "sparsify"
    ratio: float = 0.01
    block_size: int = 1024
    use_pallas: bool = False

    def encode(self, x, key):
        flat = x.reshape(-1)
        n = flat.shape[0]
        if self.use_pallas:
            from repro.kernels import ops as kops
            vals, idx = kops.block_topk_pack(
                x, ratio=self.ratio, block_size=self.block_size)
            return vals, {"idx": idx}, _SparseMeta(
                tuple(x.shape), n, vals.shape[1], "pallas",
                nb=vals.shape[0], bs=self.block_size)
        if n <= self.block_size:          # one block: exact global top-k
            return TopKCodec(ratio=self.ratio).encode(x, key)
        bs = self.block_size
        assert bs <= np.iinfo(np.uint16).max + 1, "uint16 block-local indices"
        nb = -(-n // bs)
        k = max(1, int(np.ceil(self.ratio * bs)))
        padded = jnp.pad(flat, (0, nb * bs - n))
        blocks = padded.reshape(nb, bs)
        _, idx = jax.lax.top_k(jnp.abs(blocks), k)
        vals = jnp.take_along_axis(blocks, idx, axis=1)
        return vals, {"idx": idx.astype(jnp.uint16)}, _SparseMeta(
            tuple(x.shape), n, k, "block", nb=nb, bs=bs)

    def decode(self, carrier, aux, meta):
        if meta.mode in ("dense", "global"):
            return TopKCodec(ratio=self.ratio).decode(carrier, aux, meta)
        if meta.mode == "pallas":
            from repro.kernels import ops as kops
            return kops.block_topk_unpack(carrier, aux["idx"], meta.n,
                                          meta.shape,
                                          block_size=self.block_size)
        idx = aux["idx"].astype(jnp.int32)
        blocks = jnp.zeros((meta.nb, meta.bs), carrier.dtype)
        blocks = blocks.at[jnp.arange(meta.nb)[:, None], idx].set(carrier)
        return blocks.reshape(-1)[:meta.n].reshape(meta.shape)

    def delta_for_n(self, n):
        return self.ratio

    def out_size(self, n):
        # the pallas path packs every leaf block-wise (no global fallback
        # for small leaves, matching its encode)
        if n <= self.block_size and not self.use_pallas:
            return TopKCodec(ratio=self.ratio).out_size(n)
        nb = max(1, -(-n // self.block_size))
        k = max(1, int(np.ceil(self.ratio * self.block_size)))
        return nb * k

    def sidecar_formula_bytes(self, n):
        if n <= self.block_size and not self.use_pallas:
            return TopKCodec(ratio=self.ratio).sidecar_formula_bytes(n)
        return self.out_size(n) * 2    # uint16 block-local indices


@dataclass(frozen=True)
class RandKCodec(Codec):
    """Exactly-k random coordinates; the index set is regenerated from the
    shared 8-byte PRNG key at decode, so the sidecar is the key alone."""

    name: str = "randk"
    kind: str = "sparsify"
    ratio: float = 0.01

    def encode(self, x, key):
        flat = x.reshape(-1)
        n = flat.shape[0]
        k = max(1, int(np.ceil(self.ratio * n)))
        if k >= n:
            return x, {}, _SparseMeta(tuple(x.shape), n, n, "dense")
        idx = _randk_indices(key, n, k)
        vals = flat[idx]
        return vals, {"key": key}, _SparseMeta(tuple(x.shape), n, k, "global")

    def decode(self, carrier, aux, meta):
        if meta.mode == "dense":
            return carrier
        idx = _randk_indices(aux["key"], meta.n, meta.k)
        return _scatter_flat(carrier, idx, meta)

    def delta_for_n(self, n):
        return self.ratio

    def out_size(self, n):
        k = max(1, int(np.ceil(self.ratio * n)))
        return min(k, n)

    def sidecar_formula_bytes(self, n):
        return 0 if self.out_size(n) >= n else 8   # the PRNG key


@dataclass(frozen=True)
class QSGDCodec(Codec):
    """QSGD stochastic quantization; int8/int16 signed grid + f32 scale.

    The carrier is ``sign(x)·q`` materialized at the narrowest integer
    dtype that holds ±levels; decode scales it by ``norm/levels/(1+ω)``,
    the 1/(1+ω) that makes the stage a contraction.
    """

    name: str = "qsgd"
    kind: str = "quantize"
    levels: int = 16

    def _wire_dtype(self):
        return jnp.int8 if self.levels <= np.iinfo(np.int8).max else jnp.int16

    def encode(self, x, key):
        n = int(np.prod(x.shape))
        f = x.astype(jnp.float32)
        norm = jnp.linalg.norm(f.reshape(-1)) + 1e-12
        scaled = jnp.abs(f) / norm * self.levels
        lower = jnp.floor(scaled)
        prob = scaled - lower
        rnd = jax.random.uniform(key, x.shape)
        q = lower + (rnd < prob).astype(jnp.float32)
        carrier = (jnp.sign(f) * q).astype(self._wire_dtype())
        meta = _QuantMeta(tuple(x.shape), n, str(x.dtype),
                          levels=self.levels,
                          omega=_qsgd_omega(n, self.levels))
        return carrier, {"scale": norm.reshape(1)}, meta

    def decode(self, carrier, aux, meta):
        norm = aux["scale"][0]
        out = (carrier.astype(jnp.float32) * norm / meta.levels
               / (1.0 + meta.omega))
        return out.astype(meta.in_dtype)

    def delta_for_n(self, n):
        return 1.0 / (1.0 + _qsgd_omega(n, self.levels))

    def sidecar_formula_bytes(self, n):
        return 4                      # the f32 norm

    def carrier_formula_bytes(self, n, elem_bytes: int = 4):
        bits = max(1, int(np.ceil(np.log2(self.levels + 1))) + 1)
        return -(-n * bits // 8)


@dataclass(frozen=True)
class SignCodec(Codec):
    """Ternary sign code: bit-packed sign plane + nonzero-mask plane +
    mean-magnitude scale (2 bits/entry on the wire).

    The explicit zero symbol makes decode reproduce the dense sign operator
    bitwise — ``sign(0)·scale = 0`` included. A sign-only 1-bit plane
    would inject ±scale mass at exact-zero coordinates (common in packed
    carriers: a block with fewer than k nonzeros pads with zeros), which
    the contraction analysis never produced.
    """

    name: str = "sign"
    kind: str = "quantize"

    def encode(self, x, key):
        n = int(np.prod(x.shape))
        flat = x.reshape(-1)
        scale = jnp.mean(jnp.abs(x))
        bits = jnp.packbits((flat > 0).astype(jnp.uint8))
        mask = jnp.packbits((flat != 0).astype(jnp.uint8))
        meta = _QuantMeta(tuple(x.shape), n, str(x.dtype))
        return bits, {"mask": mask,
                      "scale": scale.reshape(1).astype(jnp.float32)}, meta

    def decode(self, carrier, aux, meta):
        pos = jnp.unpackbits(carrier, count=meta.n).astype(jnp.float32)
        nz = jnp.unpackbits(aux["mask"], count=meta.n).astype(jnp.float32)
        sgn = (2.0 * pos - 1.0) * nz           # {-1, 0, +1}, exact in f32
        out = sgn.astype(meta.in_dtype) * aux["scale"][0].astype(
            meta.in_dtype)
        return out.reshape(meta.shape)

    def delta_for_n(self, n):
        return 1e-3                   # kurtosis-dependent; loose bound

    def sidecar_formula_bytes(self, n):
        return 4 + -(-n // 8)         # scale + nonzero-mask plane

    def carrier_formula_bytes(self, n, elem_bytes: int = 4):
        return -(-n // 8)             # sign plane


class LeafPayload(NamedTuple):
    """Wire buffers for one leaf: final carrier + per-stage sidecars.

    The buffers fully determine the decode — byte-exact round-trip accounting.
    """
    wire: Any                         # last stage's carrier buffer
    aux: Tuple[Dict[str, Any], ...]   # sidecars, one dict per stage


class LeafSpec(NamedTuple):
    """Static per-leaf decode spec.

    Static and hashable — safe jit cache-key material, pure in the input pytree structure.
    """
    shape: Tuple[int, ...]
    dtype: str
    passthrough: bool                 # min_dense_size leaves ride dense
    metas: Tuple[Any, ...] = ()       # per-stage static metas
    stages: Tuple[Any, ...] = ()      # per-leaf stage override (layer
    #                                   pipelines); () -> payload.stages


def leaf_stages(payload: "WirePayload", i: int) -> Tuple[Any, ...]:
    """The codec stages that encoded leaf ``i`` — per-leaf override when a
    :class:`PerLayerPipeline` routed the leaf, else the pipeline default."""
    return payload.specs[i].stages or payload.stages


def _buffer_bytes(buf) -> int:
    return int(np.prod(buf.shape)) * np.dtype(buf.dtype).itemsize


@jax.tree_util.register_pytree_node_class
class WirePayload:
    """The packed representation that crosses the link (DESIGN.md §2).

    A registered pytree: the value/index/scale buffers are children (so
    payloads pass through jit / scan / collectives), everything needed to
    invert them — treedef, per-leaf specs, the codec stages — is static
    aux data. ``measured_bytes()`` sums the actual buffer footprints
    (uint16 indices, int8 quantized grids, packed sign bits, 8-byte rand-k
    keys), replacing the closed-form estimate as the source of truth; the
    formula table stays available as a cross-check via
    :meth:`CompressionPipeline.formula_bytes`.

    Byte counts derive deterministically from shapes/dtypes and are exact-gated in CI.
    """

    def __init__(self, entries, treedef, specs, stages):
        self.entries = tuple(entries)     # one LeafPayload per leaf
        self.treedef = treedef
        self.specs = tuple(specs)
        self.stages = tuple(stages)

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        return (self.entries,), (self.treedef, self.specs, self.stages)

    @classmethod
    def tree_unflatten(cls, aux, children):
        treedef, specs, stages = aux
        return cls(children[0], treedef, specs, stages)

    # -- accounting --------------------------------------------------------
    def per_leaf_bytes(self):
        """Measured wire bytes per leaf (list aligned with the treedef)."""
        out = []
        for entry in self.entries:
            b = _buffer_bytes(entry.wire)
            for aux in entry.aux:
                b += sum(_buffer_bytes(v) for v in aux.values())
            out.append(b)
        return out

    def measured_bytes(self) -> int:
        """Total bytes on the wire, computed from the actual buffers."""
        return int(sum(self.per_leaf_bytes()))


def _stage_key(leaf_key, si: int):
    """Stage 0 uses the leaf key directly; later stochastic stages fold in
    their index."""
    return leaf_key if si == 0 else jax.random.fold_in(leaf_key, si)


@dataclass(frozen=True)
class CompressionPipeline:
    """Chainable codec stages with a materialized wire format.

    ``__call__`` is ``decode(encode(x))``. Deltas compose multiplicatively
    (Gong & Simeone '22: a δ₁-contraction followed by a δ₂-contraction of
    its output is a δ₁·δ₂-contraction).

    Purity: the encode/decode pair is deterministic given the stage key, and wire bytes are an exact static function of the input structure.
    """

    stages: Tuple[Codec, ...] = (BlockTopKCodec(),)
    min_dense_size: int = 0   # leaves with fewer elements are passed through

    @property
    def spec(self) -> str:
        return "|".join(s.name for s in self.stages)

    # -- per-leaf routing hooks (overridden by PerLayerPipeline) -----------
    def _resolve_stages(self, path_str: str) -> Tuple[Codec, ...]:
        """Stages for the leaf at ``path_str`` (keystr of its tree path)."""
        return self.stages

    # -- encode / decode ---------------------------------------------------
    def _encode_leaf(self, stages, x, v, leaf_key):
        """Encode one leaf through ``stages``. ``v`` is None for plain
        ``encode``; otherwise the residual ``x - v`` is the stage-0 input
        (materialized here — :class:`FusedCodec` overrides this seam)."""
        src = x if v is None else x - v.astype(x.dtype)
        carrier, auxes, metas = src, [], []
        for si, stage in enumerate(stages):
            carrier, aux, meta = stage.encode(carrier,
                                              _stage_key(leaf_key, si))
            auxes.append(aux)
            metas.append(meta)
        return carrier, tuple(auxes), tuple(metas)

    def _encode_impl(self, tree, vtree, key) -> WirePayload:
        leaves_p, treedef = jax.tree_util.tree_flatten_with_path(tree)
        vleaves = (jax.tree.leaves(vtree) if vtree is not None
                   else [None] * len(leaves_p))
        keys = jax.random.split(key, len(leaves_p))
        entries, specs = [], []
        for (path, x), v, leaf_key in zip(leaves_p, vleaves, keys):
            stages = self._resolve_stages(jax.tree_util.keystr(path))
            per_leaf = () if stages is self.stages else tuple(stages)
            if self.min_dense_size and x.size <= self.min_dense_size:
                wire = x if v is None else x - v.astype(x.dtype)
                entries.append(LeafPayload(wire=wire, aux=()))
                specs.append(LeafSpec(tuple(x.shape), str(x.dtype), True))
                continue
            carrier, auxes, metas = self._encode_leaf(stages, x, v, leaf_key)
            entries.append(LeafPayload(wire=carrier, aux=auxes))
            specs.append(LeafSpec(tuple(x.shape), str(x.dtype), False,
                                  metas, per_leaf))
        return WirePayload(entries, treedef, specs, self.stages)

    def encode(self, tree, key) -> WirePayload:
        return self._encode_impl(tree, None, key)

    def encode_pair(self, theta, v, key) -> WirePayload:
        """Encode the residual ``theta - v`` handed as its two operands.

        The round functions call this instead of materializing the delta
        themselves (DESIGN.md §13): the base pipeline forms the residual
        per leaf here (two-pass path, bitwise-identical to
        ``encode(tree_map(lambda t, v: t - v.astype(t.dtype), ...))``);
        :class:`FusedCodec` lowers eligible leaves to the fused Pallas
        kernels so the dense residual never reaches HBM.
        """
        return self._encode_impl(theta, v, key)

    def decode(self, payload: WirePayload):
        leaves = []
        for i, (entry, spec) in enumerate(zip(payload.entries,
                                              payload.specs)):
            if spec.passthrough:
                leaves.append(entry.wire)
                continue
            carrier = entry.wire
            for stage, aux, meta in reversed(list(zip(
                    leaf_stages(payload, i), entry.aux, spec.metas))):
                carrier = stage.decode(carrier, aux, meta)
            leaves.append(carrier)
        return jax.tree.unflatten(payload.treedef, leaves)

    def __call__(self, tree, key):
        return self.decode(self.encode(tree, key))

    # -- contraction -------------------------------------------------------
    @property
    def delta(self) -> float:
        """Conservative (shape-free) composed contraction constant."""
        d = 1.0
        for s in self.stages:
            d *= (s.ratio if s.kind == "sparsify"
                  else 1.0 if s.kind == "identity" else 1e-3)
        return d

    def delta_for(self, tree) -> float:
        """Shape-aware composed delta: min over leaves of the product of
        per-stage contractions on the carrier sizes actually seen (each
        leaf through the stages that actually encode it)."""
        deltas = [1.0]
        for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
            n = int(np.prod(x.shape))
            if self.min_dense_size and n <= self.min_dense_size:
                continue
            d = 1.0
            for stage in self._resolve_stages(jax.tree_util.keystr(path)):
                d *= stage.delta_for_n(n)
                n = stage.out_size(n)
            deltas.append(d)
        return float(min(deltas))

    # -- wire accounting ---------------------------------------------------
    def wire_bytes(self, tree) -> int:
        """Measured bytes for ``tree`` (static: traces encode shapes only);
        :meth:`formula_bytes` is the closed-form cross-check."""
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        specs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        payload = jax.eval_shape(self.encode, specs, key)
        return payload.measured_bytes()

    def formula_bytes(self, tree, elem_bytes: int = 4) -> int:
        """Closed-form byte table (the pre-codec estimate), kept as the
        cross-check for :meth:`wire_bytes`: sidecars per stage plus the
        final carrier at the last stage's encoding."""
        total = 0
        for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
            n = int(np.prod(x.shape))
            if self.min_dense_size and n <= self.min_dense_size:
                total += n * elem_bytes
                continue
            carrier_bytes = n * elem_bytes      # stage-less: dense
            for stage in self._resolve_stages(jax.tree_util.keystr(path)):
                total += stage.sidecar_formula_bytes(n)
                carrier_bytes = stage.carrier_formula_bytes(n, elem_bytes)
                n = stage.out_size(n)
            total += carrier_bytes
        return total


# ==========================================================================
# Fused compress-in-update (DESIGN.md §13)
# ==========================================================================

def _lower_stage0(stages: Tuple[Codec, ...]) -> Tuple[Codec, ...]:
    """Normalize a leading block-top-k stage onto the Pallas pack path.

    The jnp encode emits survivors in ``top_k`` descending-magnitude slot
    order while the pack kernel emits two-tier prefix-rank order — the
    same *set*, different slot permutation. Later stochastic stages bind
    uniforms to slot positions, so the fused path and its ``fused=False``
    oracle must share the kernel's ordering for bitwise equality: both
    run stage 0 with ``use_pallas=True``.
    """
    if stages and isinstance(stages[0], BlockTopKCodec):
        return (replace(stages[0], use_pallas=True),) + tuple(stages[1:])
    return tuple(stages)


def _qsgd_encode_pallas(stage: QSGDCodec, x, key):
    """`QSGDCodec.encode` with the grid arithmetic in the Pallas kernel
    (bitwise-identical carrier/scale under a common jit context)."""
    from repro.kernels import ops as kops
    n = int(np.prod(x.shape))
    grid, norm = kops.qsgd_quantize_carrier(
        x, key, levels=stage.levels, out_dtype=stage._wire_dtype())
    meta = _QuantMeta(tuple(x.shape), n, str(x.dtype), levels=stage.levels,
                      omega=_qsgd_omega(n, stage.levels))
    return grid, {"scale": norm.reshape(1)}, meta


@dataclass(frozen=True)
class FusedCodec(CompressionPipeline):
    """Compress-in-update lowering of a codec pipeline (DESIGN.md §13).

    ``encode_pair(theta, v, key)`` lowers eligible leaves to the
    ``repro.kernels.fused_compress`` family: the residual is formed
    tile-locally inside the pack kernel (one read of theta and v, wire-
    sized writes — the dense delta never reaches HBM), and a trailing
    QSGD stage quantizes the packed carrier in a second wire-sized
    kernel. Eligibility is per leaf: stage 0 must be the Pallas
    block-top-k codec; anything else (passthrough leaves, exotic stage
    orders) falls back transparently to the two-pass encode. With
    ``fused=False`` the same object IS the two-pass bitwise reference
    oracle — identical stages, identical keys, residual materialized.
    """

    fused: bool = True

    @classmethod
    def wrap(cls, pipeline: CompressionPipeline, fused: bool = True,
             **kw) -> "FusedCodec":
        return cls(stages=_lower_stage0(pipeline.stages),
                   min_dense_size=pipeline.min_dense_size,
                   fused=fused, **kw)

    def _encode_leaf(self, stages, x, v, leaf_key):
        s0 = stages[0] if stages else None
        eligible = (v is not None and self.fused
                    and isinstance(s0, BlockTopKCodec) and s0.use_pallas)
        if not eligible:
            return super()._encode_leaf(stages, x, v, leaf_key)
        from repro.kernels import ops as kops
        vals, idx = kops.fused_delta_pack(
            x, v, ratio=s0.ratio, block_size=s0.block_size)
        carrier = vals
        auxes = [{"idx": idx}]
        metas = [_SparseMeta(tuple(x.shape), x.size, vals.shape[1],
                             "pallas", nb=vals.shape[0], bs=s0.block_size)]
        for si in range(1, len(stages)):
            stage = stages[si]
            skey = _stage_key(leaf_key, si)
            if isinstance(stage, QSGDCodec):
                carrier, aux, meta = _qsgd_encode_pallas(stage, carrier,
                                                         skey)
            else:
                carrier, aux, meta = stage.encode(carrier, skey)
            auxes.append(aux)
            metas.append(meta)
        return carrier, tuple(auxes), tuple(metas)


@dataclass(frozen=True)
class PerLayerPipeline(FusedCodec):
    """Per-layer adaptive pipelines (``FedConfig.layer_pipelines``).

    ``rules`` is an ordered tuple of ``(pattern, pipeline)`` pairs; the
    first pattern that substring-matches the leaf's tree path (the
    ``jax.tree_util.keystr`` form, e.g. ``"['embed_tokens']['kernel']"``
    — same path-matching style as ``models/sharding_hints.py``) routes
    that leaf through its pipeline's stages; ``"*"`` (or ``""``) matches
    everything. Unmatched leaves use the base ``stages``. Decode reads
    the per-leaf stage tuple recorded in each :class:`LeafSpec`, so
    payloads stay self-describing (transport keep-masks included).

    Routing is static per leaf path — pure in the pytree structure, so jit traces one stable graph.
    """

    rules: Tuple[Tuple[str, CompressionPipeline], ...] = ()

    def _resolve_stages(self, path_str: str) -> Tuple[Codec, ...]:
        for pat, pipe in self.rules:
            if pat in ("*", "") or pat in path_str:
                return pipe.stages
        return self.stages


def parse_layer_rules(spec: str) -> Tuple[Tuple[str, str], ...]:
    """Parse the ``"pattern=pipeline;pattern=pipeline"`` CLI DSL, e.g.
    ``"embed=qsgd;attn=block_topk|qsgd"``, into (pattern, spec) pairs."""
    rules = []
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        pat, eq, sub = part.partition("=")
        if not eq or not sub.strip():
            raise ValueError(
                f"layer rule {part!r} is not 'pattern=pipeline'")
        rules.append((pat.strip(), sub.strip()))
    return tuple(rules)


_CODEC_FACTORIES: Dict[str, Callable[..., Codec]] = {
    "identity": lambda ratio, block_size, levels: IdentityCodec(),
    "topk": lambda ratio, block_size, levels: TopKCodec(ratio=ratio),
    "block_topk": lambda ratio, block_size, levels: BlockTopKCodec(
        ratio=ratio, block_size=block_size),
    "randk": lambda ratio, block_size, levels: RandKCodec(ratio=ratio),
    "qsgd": lambda ratio, block_size, levels: QSGDCodec(levels=levels),
    "sign": lambda ratio, block_size, levels: SignCodec(),
}


def parse_pipeline(spec: str, *, ratio: float = 0.01, block_size: int = 1024,
                   qsgd_levels: int = 16,
                   min_dense_size: int = 0) -> CompressionPipeline:
    """Build a pipeline from the ``"stage|stage"`` DSL, e.g.
    ``"block_topk|qsgd"``. Validates composition order: at most one
    sparsifier, and it must precede any quantizer (quantized carriers
    cannot be re-sparsified by magnitude)."""
    stages = []
    for nm in (s.strip() for s in spec.split("|")):
        if nm not in _CODEC_FACTORIES:
            raise ValueError(
                f"unknown codec {nm!r}; known: {sorted(_CODEC_FACTORIES)}")
        stages.append(_CODEC_FACTORIES[nm](ratio, block_size, qsgd_levels))
    n_sparse = sum(1 for s in stages if s.kind == "sparsify")
    if n_sparse > 1:
        raise ValueError(f"at most one sparsifier per pipeline: {spec!r}")
    for i, s in enumerate(stages):
        if s.kind == "quantize" and i != len(stages) - 1:
            # a quantizer's carrier is a wire buffer (int8 grid / packed
            # bits) — no later stage can meaningfully consume it
            kind = ("sparsifier" if stages[i + 1].kind == "sparsify"
                    else "quantizer" if stages[i + 1].kind == "quantize"
                    else "stage")
            raise ValueError(
                f"quantizer must be the terminal stage ({kind} follows "
                f"{s.name!r}): {spec!r}")
    return CompressionPipeline(stages=tuple(stages),
                               min_dense_size=min_dense_size)


# --------------------------------------------------------------------------
# HBM-traffic ledger for one encode (DESIGN.md §13)
# --------------------------------------------------------------------------
#
# Counts the logical HBM traffic of the lowered encode program from static
# shapes alone (machine-independent python ints, so the numbers are
# exact-gateable in check_regression): every materialized intermediate
# costs one write of its bytes plus one read per consumer; Pallas kernels
# cost reads of their inputs and writes of their outputs. Register-tile
# temporaries inside a kernel (the fused path's residual) cost nothing —
# that is the whole point.

def _pad_rows(nb: int, mult: int = 8) -> int:
    return -(-nb // mult) * mult


def _qsgd_stage_traffic(nb: int, k: int, esize: int, wbytes: int):
    """(reads, writes) of one carrier-level QSGD stage — identical terms
    for the fused kernel and the two-pass codec stage (both O(wire))."""
    c = nb * k
    pr = _pad_rows(nb)
    r = c * esize                       # norm reduction over the carrier
    w = c * 4                           # materialized uniforms (f32)
    r += c * (esize + 4)                # row-pad reads carrier + uniforms
    w += pr * k * (esize + 4)           # padded tiles
    r += pr * k * (esize + 4) + 4       # kernel reads tiles + the norm
    w += pr * k * wbytes                # integer grid out
    r += c * wbytes                     # [:nb] slice
    w += c * wbytes + 4                 # sliced grid + the f32 scale
    return r, w


def encode_hbm_bytes(pipeline: CompressionPipeline, theta, v=None) -> dict:
    """Static per-encode HBM-byte ledger for ``encode_pair(theta, v)``.

    ``theta``/``v`` may be concrete trees or ``ShapeDtypeStruct`` trees.
    Returns reads/writes/total for the pipeline as configured, plus the
    ``2p reads + wire writes`` lower bound (one read of theta and v, the
    payload's measured bytes written) the tentpole is judged against.
    """
    from repro.kernels.pack import ROWS_PER_TILE
    fused = bool(getattr(pipeline, "fused", False))
    tleaves = jax.tree_util.tree_flatten_with_path(theta)[0]
    vleaves = (jax.tree.leaves(v) if v is not None else
               [x for _, x in tleaves])
    reads = writes = lb_reads = lb_writes = 0
    for (path, x), vx in zip(tleaves, vleaves):
        n = int(np.prod(x.shape))
        esize = np.dtype(x.dtype).itemsize
        vsize = np.dtype(vx.dtype).itemsize
        stages = pipeline._resolve_stages(jax.tree_util.keystr(path))
        s0 = stages[0] if stages else None
        lb_reads += n * (esize + vsize)
        if pipeline.min_dense_size and n <= pipeline.min_dense_size:
            # passthrough: delta materializes at wire size either way
            reads += n * (esize + vsize)
            writes += n * esize
            lb_writes += n * esize
            continue
        eligible = isinstance(s0, BlockTopKCodec) and s0.use_pallas
        bs = s0.block_size if eligible else 0
        k = max(1, int(np.ceil(s0.ratio * bs))) if eligible else 0
        nb = max(1, -(-n // bs)) if eligible else 0
        if eligible and fused:
            tile = ROWS_PER_TILE * bs
            n_head = (n // tile) * tile
            # aligned prefix: a pure reshape — the kernel's read of theta
            # and v is the only O(p) traffic
            reads += n_head * (esize + vsize)
            writes += (n_head // bs) * k * (esize + 4)
            if n_head < n or n_head == 0:
                tail = n - n_head
                reads += tail * (esize + vsize)      # build padded tiles
                writes += tile * (esize + vsize)
                reads += tile * (esize + vsize)      # kernel reads them
                writes += ROWS_PER_TILE * k * (esize + 4)
            rows = (n_head // bs) + (ROWS_PER_TILE if (n_head < n or
                                                       n_head == 0) else 0)
            reads += rows * k * (esize + 4)          # concat + [:nb] slice
            writes += nb * k * (esize + 2)           # vals + uint16 idx
        elif eligible:
            # two-pass: materialize delta, pad-copy, pack kernel
            reads += n * (esize + vsize)             # delta read
            writes += n * esize                      # delta write
            pr = _pad_rows(-(-n // bs), ROWS_PER_TILE)
            pp = pr * bs
            reads += n * esize                       # _pad_to_2d copy
            writes += pp * esize
            reads += pp * esize                      # pack kernel read
            writes += pr * k * (esize + 4)           # vals + int32 idx
            reads += nb * k * (esize + 4)            # slice + narrow
            writes += nb * k * (esize + 2)
        else:
            # ineligible stage 0 (both modes fall back identically):
            # delta + one read/write per stage at its carrier size
            reads += n * (esize + vsize)
            writes += n * esize
            cn, ce = n, esize
            for stage in stages:
                reads += cn * ce
                cn = stage.out_size(cn)
                ce = ce if stage.kind != "quantize" else 1
                writes += cn * ce + stage.sidecar_formula_bytes(n)
            stages = ()
        for stage in stages[1:] if eligible else ():
            if isinstance(stage, QSGDCodec):
                wb = np.dtype(stage._wire_dtype()).itemsize
                r, w = _qsgd_stage_traffic(nb, k, esize, wb)
            else:   # e.g. sign: one pass over the carrier, packed out
                r = nb * k * esize
                w = stage.carrier_formula_bytes(nb * k) + \
                    stage.sidecar_formula_bytes(nb * k)
            reads += r
            writes += w
    # wire-writes term of the bound: the payload's measured bytes
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    spec_tree = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), theta)
    lb_writes += jax.eval_shape(pipeline.encode, spec_tree,
                                key).measured_bytes()
    return {
        "read_bytes": int(reads),
        "write_bytes": int(writes),
        "hbm_bytes": int(reads + writes),
        "lower_bound_bytes": int(lb_reads + lb_writes),
    }


def make_compressor(fed_cfg):
    """Build the compression pipeline from a FedConfig.

    ``fed_cfg.compressor`` is the ``"a|b"`` codec DSL
    (:func:`parse_pipeline`). ``fed_cfg.fused_compress`` wraps the
    pipeline in a :class:`FusedCodec` (stage 0 normalized to the Pallas
    pack path — see :func:`_lower_stage0`); ``fed_cfg.layer_pipelines``
    builds a :class:`PerLayerPipeline` routing leaves by path pattern. The
    two compose.
    """
    kw = dict(
        ratio=fed_cfg.compress_ratio,
        block_size=fed_cfg.block_size,
        qsgd_levels=fed_cfg.qsgd_levels,
        min_dense_size=fed_cfg.min_dense_size,
    )
    base = parse_pipeline(fed_cfg.compressor, **kw)
    fused = fed_cfg.fused_compress
    if fed_cfg.layer_pipelines:
        rules = tuple(
            (pat, parse_pipeline(sub, **kw))
            for pat, sub in fed_cfg.layer_pipelines)
        if fused:
            rules = tuple((pat, replace(p, stages=_lower_stage0(p.stages)))
                          for pat, p in rules)
        return PerLayerPipeline(
            stages=_lower_stage0(base.stages) if fused else base.stages,
            min_dense_size=base.min_dense_size, fused=fused, rules=rules)
    if fused:
        return FusedCodec.wrap(base, fused=True)
    return base
