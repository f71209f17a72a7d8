"""Decentralized (Bayesian) FL round functions.

Implements, mesh-agnostically (leading node axis ``K`` on every leaf):

* ``cdbfl_round``  — the paper's Algorithm 1 (compressed Bayesian, L local steps)
* ``dsgld_round``  — uncompressed decentralized SGLD baseline (paper Eq. 4)
* ``cffl_round``   — CHOCO-SGD / compressed *frequentist* baseline [23]
* ``sgld_step``    — centralized SGLD oracle (paper Eq. 2)

All round functions share the signature

    round_fn(state, batches, key) -> (state', metrics)

where ``batches`` carries leading dims ``(K, L, ...)`` (local minibatch
sequences per node). They are pure and jit/pjit-safe: under ``jax.jit`` with
the node axis sharded over a mesh axis, the Ω-mixing lowers to the
collective schedule analyzed in EXPERIMENTS.md.

Every round function is topology-generic: the mixer is built from the
FedConfig's :class:`repro.config.TopologyConfig` (sparse schedule mixer for
bounded-degree graphs, dense einsum oracle otherwise — DESIGN.md §4) and
receives a per-round PRNG key, so time-varying graphs (link dropout,
gossip-pair sampling) work unchanged under jit.

Node decomposability: every stochastic stream that touches the trajectory
is derived *per node* from the round key and the node's global id
(compression keys, Langevin noise, minibatch sampling) — node k's
computation never reads another node's values outside the Ω-mixing. That
is what the paper's protocol does on real radios, and it is what lets the
same round function run with the node axis genuinely sharded: built with a
``shard_ctx`` (:class:`repro.core.gossip.ShardContext`), the mixing lowers
to explicit ``lax.ppermute`` exchange, metric reductions become ``psum``,
and per-node results are bitwise identical to the single-device run.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.compression import Compressor
from repro.core.fed_state import FedState
from repro.core.gossip import (ShardContext, ShardMixStats,
                               resolve_participation)
from repro.core.transport import (TRANSPORT_SALT, LossyTransport,
                                  TransportMetrics, resolve_transport)
from repro.utils.tree import tree_count, tree_random_normal


def _transport_link_probs(transport: Optional[LossyTransport]):
    """The gossip-layer hook: per-edge outage probabilities from the
    transport's SNR model (None when no link-level loss is configured)."""
    if transport is not None and transport.has_link_outage:
        return transport.outage_probs
    return None


def _default_mixer(omega, fed_cfg, link_probs=None):
    from repro.core.gossip import make_mixer
    from repro.core.topology import resolve_topology
    import numpy as _np
    return make_mixer(_np.asarray(omega), config=resolve_topology(fed_cfg),
                      link_probs=link_probs)


def _resolve_mixer(omega, fed_cfg, mixer, shard_ctx: Optional[ShardContext],
                   transport: Optional[LossyTransport] = None):
    """Pick the mixing lowering: shard (ppermute), explicit, or default.

    Returns ``(mix_fn, ShardMixStats | None)`` — stats only exist on the
    shard path, where cross/intra-shard rows are statically known.
    """
    link_probs = _transport_link_probs(transport)
    if shard_ctx is not None:
        if mixer is not None:
            raise ValueError("pass either mixer= or shard_ctx=, not both")
        from repro.core.gossip import make_shard_mixer
        from repro.core.topology import resolve_topology
        import numpy as _np
        return make_shard_mixer(_np.asarray(omega), shard_ctx,
                                config=resolve_topology(fed_cfg),
                                link_probs=link_probs)
    if mixer is None:
        return _default_mixer(omega, fed_cfg, link_probs), None
    if link_probs is not None:
        raise ValueError("an explicit mixer= cannot be combined with a "
                         "transport SNR link-outage model; build the mixer "
                         "with make_mixer(link_probs=...) instead")
    from repro.core.gossip import as_keyed_mixer
    return as_keyed_mixer(mixer), None


LossFn = Callable[[Any, Any, jax.Array], Tuple[jax.Array, Any]]


# --------------------------------------------------------------------------
# Shared pieces
# --------------------------------------------------------------------------

def _local_sgd(params, batches_l, key, loss_fn: LossFn, eta: float,
               prior_weight: float, data_scale: float, num_steps_static: int):
    """L plain SGD steps on one node (paper Eq. 5). ``batches_l`` leads with L.

    The gradient is of f_k (paper Eq. 3): data_scale * NLL + prior_weight *
    N(0,I) prior term. ``data_scale`` converts the minibatch mean NLL into an
    estimate of the local-sum NLL (E_k); ``prior_weight`` is 1/K so the K
    nodes jointly represent one prior.
    """

    def step(carry, batch):
        p, k = carry
        k, ksub = jax.random.split(k)

        def f(pp):
            nll, aux = loss_fn(pp, batch, ksub)
            prior = sum(
                jnp.sum(jnp.square(x.astype(jnp.float32)))
                for x in jax.tree.leaves(pp)
            )
            return data_scale * nll + 0.5 * prior_weight * prior, aux

        (loss, aux), grads = jax.value_and_grad(f, has_aux=True)(p)
        p = jax.tree.map(lambda x, g: x - eta * g.astype(x.dtype), p, grads)
        return (p, k), loss

    (params, _), losses = jax.lax.scan(step, (params, key), batches_l,
                                       length=num_steps_static)
    return params, losses


def _langevin_noise(key, tree, eta: float, temperature: float, node_ids):
    """Per-node Langevin noise: node k draws from ``fold_in(key, k)``.

    Each node's draw depends only on its global id, so the same values come
    out whether the node axis is one vmapped block or sharded over a mesh.
    """
    scale = jnp.sqrt(2.0 * eta * temperature)
    keys = _node_keys_for(key, node_ids)
    return jax.vmap(
        lambda k, t: tree_random_normal(k, t, scale=scale, dtype=jnp.float32)
    )(keys, tree)


class RoundMetrics(NamedTuple):
    """Per-round scalar metrics, reduced on device; a pure function of the round's inputs."""
    loss: jax.Array            # (K, L) local losses (shard-local under SPMD)
    consensus_error: jax.Array  # scalar: mean ||θ_k - θ̄||²
    delta_norm: jax.Array      # scalar: mean ||Δθ_k||²
    wire_bytes: jax.Array      # scalar: bytes/node/round on the wire
                               # (measured from the packed payload when the
                               # compressor is a CompressionPipeline)
    cross_bytes: Any = 0.0     # scalar: bytes/node/round the mixing moved
                               # *between shards* (ppermute/all-gather rows
                               # × row bytes); 0 off the shard path
    # lossy-transport accounting (0 when no transport is configured):
    offered_bytes: Any = 0.0   # scalar: on-air bytes/node/round offered to
                               # the link (payload + frame headers,
                               # retransmissions included under ARQ)
    delivered_bytes: Any = 0.0  # scalar: bytes/node/round whose frames
                               # survived the erasure draws
    airtime_s: Any = 0.0       # scalar: TX airtime/node/round (LoRa ToA
                               # under cfg.toa, flat phy_rate otherwise)
    energy_j: Any = 0.0        # scalar: TX energy/node/round at tx_power
    # reliability / barrier-free accounting (defaults = ideal barrier):
    retransmits: Any = 0.0     # scalar: ARQ frame re-sends/node/round
    abandoned_bytes: Any = 0.0  # scalar: bytes/node/round never delivered
                               # after every ARQ attempt (ride the residual)
    participation: Any = 1.0   # (K,) {0,1} participation vector of the
                               # round (replicated across shards); scalar 1
                               # when no participation model is configured


def _node_ids(local_k: int, shard_ctx: Optional[ShardContext]) -> jax.Array:
    """Global node ids of the rows this program instance holds."""
    if shard_ctx is None:
        return jnp.arange(local_k, dtype=jnp.int32)
    return shard_ctx.node_ids(local_k)


def _node_keys_for(key, node_ids) -> jax.Array:
    """One PRNG key per node, from the round key and the global node id."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(node_ids)


def _compress_exchange(compressor, theta, v, key, node_ids,
                       transport: Optional[LossyTransport] = None):
    """Run Q per node over the residual ``theta - v``, optionally through
    the lossy frame transport; return ``(delta_v, delta_mix, bytes/node,
    tx)``.

    The residual is handed to the compressor as its two operands rather
    than precomputed: pipelines encode via ``encode_pair(theta, v, key)``,
    so a :class:`~repro.core.compression.FusedCodec` can form the delta
    tile-locally inside the pack kernel and the dense residual never
    reaches HBM (DESIGN.md §13). The base pipeline's ``encode_pair``
    materializes ``t - v.astype(t.dtype)`` per leaf — bitwise-identical
    to the old precomputed-delta call on every engine.

    Node k's rows are encoded under ``fold_in(key, k)`` — its compression
    (top-k selection, QSGD norm, rand-k index set) depends only on its own
    residual, as on a real radio. Pipelines (anything with ``encode``) go
    through the materialized wire format: ``encode -> measured_bytes ->
    decode``; legacy Compressors keep the dense-masked call with the
    closed-form byte table. The payload buffers carry the local node axis,
    so dividing by the local node count gives the per-node figure the
    paper reports (identical on every shard).

    With a transport, the decoded delta is masked by the per-frame erasure
    draws (keys from ``fold_in(key, TRANSPORT_SALT)`` then the global node
    id — a stream separate from kql/knoise/kmix, identical across
    engines). ``delta_mix`` is the *delivered* delta (what the neighbors
    integrate); ``delta_v`` is what the sender's control sequence absorbs:
    equal to ``delta_mix`` under error feedback — lost frames stay in the
    next round's residual θ - v and are re-offered to the compressor — or
    the full lossless decode without it (the sender then believes
    everything arrived, and v/v̄ desynchronize). ``tx`` carries per-node
    :class:`TransportMetrics` arrays, or None when no transport applies.
    """
    keys = _node_keys_for(key, node_ids)
    local_k = node_ids.shape[0]
    if hasattr(compressor, "encode_pair"):
        with jax.named_scope("encode"):
            payload = jax.vmap(compressor.encode_pair)(theta, v, keys)
        wire = jnp.float32(payload.measured_bytes() / local_k)
        with jax.named_scope("decode"):
            if transport is None:
                delta = jax.vmap(compressor.decode)(payload)
                return delta, delta, wire, None
            kloss = jax.random.fold_in(key, TRANSPORT_SALT)
            tkeys = _node_keys_for(kloss, node_ids)
            delta_full, delta_del, tx = jax.vmap(
                partial(transport.deliver, compressor))(payload, tkeys,
                                                        node_ids)
        delta_v = delta_del if transport.error_feedback else delta_full
        return delta_v, delta_del, wire, tx
    # a dense-masked Compressor encodes and decodes in one call
    with jax.named_scope("encode"):
        residual = jax.tree.map(lambda t, vv: t - vv.astype(t.dtype), theta, v)
        delta = jax.vmap(compressor)(residual, keys)
    wire = compressor.wire_bytes(jax.tree.map(lambda x: x[0], residual))
    return delta, delta, jnp.float32(wire), None


def _reduce_transport(tx: Optional[TransportMetrics],
                      shard_ctx: Optional[ShardContext], num_nodes: int
                      ) -> TransportMetrics:
    """Global per-node means of the per-node transport metric arrays.

    Delivered/offered byte counts are integer-valued f32 well below 2^24,
    so the sums (and psums) are exact and identical across engines.
    """
    if tx is None:
        return TransportMetrics.zero()
    return TransportMetrics(
        offered=_allsum(jnp.sum(tx.offered), shard_ctx) / num_nodes,
        delivered=_allsum(jnp.sum(tx.delivered), shard_ctx) / num_nodes,
        airtime_s=_allsum(jnp.sum(tx.airtime_s), shard_ctx) / num_nodes,
        energy_j=_allsum(jnp.sum(tx.energy_j), shard_ctx) / num_nodes,
        retransmits=_allsum(jnp.sum(tx.retransmits), shard_ctx) / num_nodes,
        abandoned=_allsum(jnp.sum(tx.abandoned), shard_ctx) / num_nodes,
    )


def _mask_transport(tx: Optional[TransportMetrics], p_local):
    """A non-participating node transmits nothing: zero its rows in the
    per-node transport metric arrays before the global reduction."""
    if tx is None or p_local is None:
        return tx
    return TransportMetrics(*(jnp.asarray(f) * p_local for f in tx))


def _participation_freeze(p_local, new_tree, old_tree):
    """Barrier-free round semantics for node state: a node that skipped
    the round contributes nothing and absorbs nothing — its params and
    control sequences carry over unchanged (stale state), exactly as if
    the round never happened for it."""
    def leaf(n, o):
        m = p_local.reshape((p_local.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(m > 0.5, n, o.astype(n.dtype))
    return jax.tree.map(leaf, new_tree, old_tree)


def _check_transport(transport: Optional[LossyTransport], compressor):
    """Frame-level loss needs the materialized wire format."""
    if (transport is not None and transport.lossy
            and not hasattr(compressor, "encode")):
        raise ValueError(
            "frame-level transport loss requires a codec pipeline "
            "(CompressionPipeline); the legacy dense-masked Compressor has "
            "no wire payload to fragment — use fed_cfg.pipeline")


def _allsum(x, shard_ctx: Optional[ShardContext]):
    """Sum over all shards (identity off the shard path)."""
    if shard_ctx is None:
        return x
    return jax.lax.psum(x, shard_ctx.axis_name)


def _consensus_error(params, shard_ctx: Optional[ShardContext] = None,
                     num_nodes: int = 0):
    if shard_ctx is None:
        def leaf(x):
            mean = jnp.mean(x.astype(jnp.float32), axis=0, keepdims=True)
            return jnp.sum(jnp.square(x.astype(jnp.float32) - mean))
        return sum(jax.tree.leaves(jax.tree.map(leaf, params)))

    def leaf(x):
        xf = x.astype(jnp.float32)
        mean = _allsum(jnp.sum(xf, axis=0, keepdims=True), shard_ctx) / num_nodes
        return _allsum(jnp.sum(jnp.square(xf - mean)), shard_ctx)
    return sum(jax.tree.leaves(jax.tree.map(leaf, params)))


def _sq_norm(tree, shard_ctx: Optional[ShardContext] = None):
    return _allsum(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
            for x in jax.tree.leaves(tree)),
        shard_ctx,
    )


def _cross_bytes(mix_stats: Optional[ShardMixStats], mixed_tree,
                 local_k: int) -> jax.Array:
    """Bytes/node/round the mixing physically moved between shards: the
    static cross-shard row count × the f32 row footprint of the mixed
    tree (the mixer exchanges f32-cast rows)."""
    if mix_stats is None:
        return jnp.float32(0.0)
    per_node = tree_count(mixed_tree) // local_k
    return jnp.float32(mix_stats.cross_rows * per_node * 4)


# --------------------------------------------------------------------------
# CD-BFL — the paper's Algorithm 1
# --------------------------------------------------------------------------

def make_cdbfl_round(loss_fn: LossFn, fed_cfg, omega, compressor: Compressor,
                     data_scale: float = 1.0, mixer=None,
                     shard_ctx: Optional[ShardContext] = None,
                     transport: Optional[LossyTransport] = None):
    """Build the jit-able CD-BFL round function.

    One round = L local SGLD-style SGD steps per node, compressed residual
    exchange, CHOCO control-variate bookkeeping, consensus correction and
    Langevin noise injection (paper Eqs. 5-9).

    ``mixer``: optional mix(tree, key)->tree override (defaults to the
    topology-aware schedule mixer from repro.core.gossip; legacy mix(tree)
    callables are adapted).

    ``shard_ctx``: when set, the round is built for execution inside a
    ``shard_map`` whose ``axis_name`` carries the node axis: the mixing is
    explicit ppermute exchange, metric reductions psum over shards, and
    per-node arithmetic is bitwise identical to the unsharded round.

    ``transport``: optional :class:`~repro.core.transport.LossyTransport`
    override (defaults to one built from ``fed_cfg.transport``; None when
    neither is set = ideal links). Frame erasure masks the exchanged delta
    and, with error feedback on, the lost mass stays in the next round's
    residual. With ``erasure=0`` and no SNR model the trajectory is
    bitwise identical to the no-transport path.
    """
    eta = fed_cfg.eta
    zeta = fed_cfg.zeta
    K = fed_cfg.num_nodes
    L = fed_cfg.local_steps
    omega = jnp.asarray(omega, jnp.float32)
    transport = resolve_transport(fed_cfg, transport)
    _check_transport(transport, compressor)
    mixer, mix_stats = _resolve_mixer(omega, fed_cfg, mixer, shard_ctx,
                                      transport)
    participation = resolve_participation(fed_cfg)
    prior_weight = 1.0 / K

    def round_fn(state: FedState, batches, key) -> Tuple[FedState, RoundMetrics]:
        kql, knoise = jax.random.split(key)
        kmix = jax.random.fold_in(key, 2)   # keeps kql/knoise streams stable
        ids = _node_ids(state.key.shape[0], shard_ctx)
        p_full = p_local = None
        if participation is not None:
            p_full = participation.mask(key, state.round)
            p_local = jnp.take(p_full, ids)
        node_keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
            state.key, state.round
        )

        # -- Eq. 5: L local steps on every node (vmapped over K) -------------
        local = partial(
            _local_sgd, loss_fn=loss_fn, eta=eta,
            prior_weight=prior_weight, data_scale=data_scale,
            num_steps_static=L,
        )
        with jax.named_scope("local_step"):
            theta_L, losses = jax.vmap(local)(state.params, batches, node_keys)

        # -- Eq. 6: compressed residual vs control sequence ------------------
        # encode -> wire payload -> decode: the packed (values, indices)
        # representation is what a real transport would ship; the mixer
        # consumes the decoded dense delta (DESIGN.md §2). theta and v go
        # in as separate operands so a fused codec never materializes the
        # dense residual (DESIGN.md §13).
        delta_v, delta, wire, tx = _compress_exchange(
            compressor, theta_L, state.v, kql, ids, transport)

        # -- Eq. 7 / Eq. 8: control sequences (stored in control_dtype) ------
        # under a lossy transport, v absorbs the *delivered* delta (error
        # feedback: lost frames stay in the next residual); delta below is
        # always the delivered one — it is what the neighbors mix in.
        with jax.named_scope("mix"):
            v_new = jax.tree.map(lambda v, d: (v + d.astype(v.dtype)),
                                 state.v, delta_v)
            mixed = mixer(delta, kmix) if p_full is None else mixer(
                delta, kmix, p_full)
            v_bar_new = jax.tree.map(lambda vb, m: (vb + m.astype(vb.dtype)),
                                     state.v_bar, mixed)

        # -- Eq. 9: consensus correction + Langevin noise --------------------
        with jax.named_scope("langevin_noise"):
            noise = _langevin_noise(knoise, theta_L, eta, fed_cfg.temperature,
                                    ids)
            params_new = jax.tree.map(
                lambda t, vb, v, n: (
                    t.astype(jnp.float32)
                    + zeta * (vb.astype(jnp.float32) - v.astype(jnp.float32))
                    + n
                ).astype(t.dtype),
                theta_L, v_bar_new, v_new, noise,
            )
        if p_local is not None:
            # barrier-free: a skipped round leaves the node's whole state
            # stale — nothing sent (tx masked below), nothing absorbed
            # (edges already dead in the mixer), local steps discarded.
            v_new = _participation_freeze(p_local, v_new, state.v)
            v_bar_new = _participation_freeze(p_local, v_bar_new, state.v_bar)
            params_new = _participation_freeze(p_local, params_new,
                                               state.params)

        with jax.named_scope("round_metrics"):
            txm = _reduce_transport(_mask_transport(tx, p_local), shard_ctx,
                                    K)
            metrics = RoundMetrics(
                loss=losses,
                consensus_error=_consensus_error(params_new, shard_ctx, K) / K,
                delta_norm=_sq_norm(delta, shard_ctx) / K,
                wire_bytes=wire,
                cross_bytes=_cross_bytes(mix_stats, delta, ids.shape[0]),
                offered_bytes=txm.offered,
                delivered_bytes=txm.delivered,
                airtime_s=txm.airtime_s,
                energy_j=txm.energy_j,
                retransmits=txm.retransmits,
                abandoned_bytes=txm.abandoned,
                participation=p_full if p_full is not None else 1.0,
            )
        new_state = FedState(
            params=params_new, v=v_new, v_bar=v_bar_new,
            opt_state=state.opt_state, key=state.key, round=state.round + 1,
        )
        return new_state, metrics

    return round_fn


# --------------------------------------------------------------------------
# DSGLD — uncompressed decentralized Bayesian baseline (Eq. 4)
# --------------------------------------------------------------------------

def make_dsgld_round(loss_fn: LossFn, fed_cfg, omega, data_scale: float = 1.0,
                     mixer=None, shard_ctx: Optional[ShardContext] = None,
                     transport: Optional[LossyTransport] = None):
    """One DSGLD iteration: θ_{k,t+1} = Σ_j ω_kj θ_j - η ∇f_k + √(2η) ξ.

    For fairness against CD-BFL with L local steps, ``batches`` still has the
    (K, L, ...) layout and we take the first minibatch (L is 1 per exchange in
    DSGLD); the driver calls it L times per CD-BFL round when matching
    gradient budgets.

    With a transport, the SNR link-outage model applies through the mixer
    seam and the dense θ exchange gets frame-level *accounting* (offered
    bytes / airtime / energy; delivered == offered). Frame-level erasure of
    the dense payload is not modeled: DSGLD has no codec or control
    sequence to absorb partial deltas — that is exactly the robustness gap
    CD-BFL's error feedback closes.
    """
    eta = fed_cfg.eta
    K = fed_cfg.num_nodes
    omega = jnp.asarray(omega, jnp.float32)
    transport = resolve_transport(fed_cfg, transport)
    mixer, mix_stats = _resolve_mixer(omega, fed_cfg, mixer, shard_ctx,
                                      transport)
    participation = resolve_participation(fed_cfg)
    prior_weight = 1.0 / K

    def round_fn(state: FedState, batches, key) -> Tuple[FedState, RoundMetrics]:
        knoise, kmix = jax.random.split(key)
        ids = _node_ids(state.key.shape[0], shard_ctx)
        p_full = p_local = None
        if participation is not None:
            p_full = participation.mask(key, state.round)
            p_local = jnp.take(p_full, ids)
        batch0 = jax.tree.map(lambda b: b[:, 0], batches)  # (K, ...)

        def node_grad(p, b, k):
            def f(pp):
                nll, _ = loss_fn(pp, b, k)
                prior = sum(
                    jnp.sum(jnp.square(x.astype(jnp.float32)))
                    for x in jax.tree.leaves(pp)
                )
                return data_scale * nll + 0.5 * prior_weight * prior
            return jax.value_and_grad(f)(p)

        node_keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
            state.key, state.round
        )
        with jax.named_scope("local_step"):
            losses, grads = jax.vmap(node_grad)(state.params, batch0,
                                                node_keys)

        # full θ exchange (uncompressed)
        with jax.named_scope("mix"):
            mixed = mixer(state.params, kmix) if p_full is None else mixer(
                state.params, kmix, p_full)
        with jax.named_scope("langevin_noise"):
            noise = _langevin_noise(knoise, state.params, eta,
                                    fed_cfg.temperature, ids)
            params_new = jax.tree.map(
                lambda m, g, n: (
                    m.astype(jnp.float32) - eta * g.astype(jnp.float32) + n
                ).astype(m.dtype),
                mixed, grads, noise,
            )
        if p_local is not None:
            params_new = _participation_freeze(p_local, params_new,
                                               state.params)
        dense_bytes = tree_count(state.params) // ids.shape[0] * 4
        with jax.named_scope("round_metrics"):
            txm = (transport.account_dense(dense_bytes)
                   if transport is not None else TransportMetrics.zero())
            if p_full is not None:
                # static per-node accounting × the realized participation
                # rate: a node that skipped the round never offered its θ
                rate = jnp.mean(p_full)
                txm = TransportMetrics(*(jnp.asarray(f) * rate for f in txm))
            metrics = RoundMetrics(
                loss=losses[:, None],
                consensus_error=_consensus_error(params_new, shard_ctx, K) / K,
                delta_norm=_sq_norm(state.params, shard_ctx) / K,
                # uncompressed θ exchange: dense fp32 payload per node
                wire_bytes=jnp.float32(dense_bytes),
                cross_bytes=_cross_bytes(mix_stats, state.params,
                                         ids.shape[0]),
                offered_bytes=txm.offered,
                delivered_bytes=txm.delivered,
                airtime_s=txm.airtime_s,
                energy_j=txm.energy_j,
                retransmits=txm.retransmits,
                abandoned_bytes=txm.abandoned,
                participation=p_full if p_full is not None else 1.0,
            )
        return (
            FedState(params_new, state.v, state.v_bar, state.opt_state,
                     state.key, state.round + 1),
            metrics,
        )

    return round_fn


# --------------------------------------------------------------------------
# CF-FL — CHOCO-SGD, compressed frequentist baseline [23]
# --------------------------------------------------------------------------

def make_cffl_round(loss_fn: LossFn, fed_cfg, omega, compressor: Compressor,
                    data_scale: float = 1.0, mixer=None,
                    shard_ctx: Optional[ShardContext] = None,
                    transport: Optional[LossyTransport] = None):
    """CD-BFL minus the Langevin noise and prior: a point-estimate learner."""
    eta = fed_cfg.eta
    zeta = fed_cfg.zeta
    K = fed_cfg.num_nodes
    L = fed_cfg.local_steps
    omega = jnp.asarray(omega, jnp.float32)
    transport = resolve_transport(fed_cfg, transport)
    _check_transport(transport, compressor)
    mixer, mix_stats = _resolve_mixer(omega, fed_cfg, mixer, shard_ctx,
                                      transport)
    participation = resolve_participation(fed_cfg)

    def round_fn(state: FedState, batches, key) -> Tuple[FedState, RoundMetrics]:
        # same key derivation as cdbfl so the compressor streams coincide
        kq, _ = jax.random.split(key)
        kmix = jax.random.fold_in(key, 2)
        ids = _node_ids(state.key.shape[0], shard_ctx)
        p_full = p_local = None
        if participation is not None:
            p_full = participation.mask(key, state.round)
            p_local = jnp.take(p_full, ids)
        node_keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
            state.key, state.round
        )
        local = partial(
            _local_sgd, loss_fn=loss_fn, eta=eta,
            prior_weight=0.0, data_scale=data_scale, num_steps_static=L,
        )
        with jax.named_scope("local_step"):
            theta_L, losses = jax.vmap(local)(state.params, batches, node_keys)

        delta_v, delta, wire, tx = _compress_exchange(
            compressor, theta_L, state.v, kq, ids, transport)
        with jax.named_scope("mix"):
            v_new = jax.tree.map(lambda v, d: (v + d.astype(v.dtype)),
                                 state.v, delta_v)
            mixed = mixer(delta, kmix) if p_full is None else mixer(
                delta, kmix, p_full)
            v_bar_new = jax.tree.map(lambda vb, m: (vb + m.astype(vb.dtype)),
                                     state.v_bar, mixed)
        params_new = jax.tree.map(
            lambda t, vb, v: (
                t.astype(jnp.float32)
                + zeta * (vb.astype(jnp.float32) - v.astype(jnp.float32))
            ).astype(t.dtype),
            theta_L, v_bar_new, v_new,
        )
        if p_local is not None:
            v_new = _participation_freeze(p_local, v_new, state.v)
            v_bar_new = _participation_freeze(p_local, v_bar_new, state.v_bar)
            params_new = _participation_freeze(p_local, params_new,
                                               state.params)
        with jax.named_scope("round_metrics"):
            txm = _reduce_transport(_mask_transport(tx, p_local), shard_ctx,
                                    K)
            metrics = RoundMetrics(
                loss=losses,
                consensus_error=_consensus_error(params_new, shard_ctx, K) / K,
                delta_norm=_sq_norm(delta, shard_ctx) / K,
                wire_bytes=wire,
                cross_bytes=_cross_bytes(mix_stats, delta, ids.shape[0]),
                offered_bytes=txm.offered,
                delivered_bytes=txm.delivered,
                airtime_s=txm.airtime_s,
                energy_j=txm.energy_j,
                retransmits=txm.retransmits,
                abandoned_bytes=txm.abandoned,
                participation=p_full if p_full is not None else 1.0,
            )
        return (
            FedState(params_new, v_new, v_bar_new, state.opt_state,
                     state.key, state.round + 1),
            metrics,
        )

    return round_fn


# --------------------------------------------------------------------------
# Centralized SGLD oracle (Eq. 2) — sanity baseline on pooled data
# --------------------------------------------------------------------------

def make_sgld_step(loss_fn: LossFn, eta: float, temperature: float = 1.0,
                   data_scale: float = 1.0):
    def step(params, batch, key):
        kgrad, knoise = jax.random.split(key)

        def f(p):
            nll, _ = loss_fn(p, batch, kgrad)
            prior = sum(
                jnp.sum(jnp.square(x.astype(jnp.float32)))
                for x in jax.tree.leaves(p)
            )
            return data_scale * nll + 0.5 * prior

        loss, grads = jax.value_and_grad(f)(params)
        # centralized oracle: no node axis, one global noise draw
        noise = tree_random_normal(knoise, params,
                                   scale=jnp.sqrt(2.0 * eta * temperature),
                                   dtype=jnp.float32)
        params = jax.tree.map(
            lambda x, g, n: (
                x.astype(jnp.float32) - eta * g.astype(jnp.float32) + n
            ).astype(x.dtype),
            params, grads, noise,
        )
        return params, loss

    return step


ALGORITHMS = {
    "cdbfl": make_cdbfl_round,
    "dsgld": make_dsgld_round,
    "cffl": make_cffl_round,
}


def make_round_fn(algorithm: str, loss_fn: LossFn, fed_cfg, omega,
                  compressor: Compressor = None, data_scale: float = 1.0,
                  mixer=None, shard_ctx: Optional[ShardContext] = None,
                  transport: Optional[LossyTransport] = None):
    if algorithm == "cdbfl":
        return make_cdbfl_round(loss_fn, fed_cfg, omega, compressor,
                                data_scale, mixer=mixer, shard_ctx=shard_ctx,
                                transport=transport)
    if algorithm == "dsgld":
        return make_dsgld_round(loss_fn, fed_cfg, omega, data_scale,
                                mixer=mixer, shard_ctx=shard_ctx,
                                transport=transport)
    if algorithm == "cffl":
        return make_cffl_round(loss_fn, fed_cfg, omega, compressor,
                               data_scale, mixer=mixer, shard_ctx=shard_ctx,
                               transport=transport)
    raise ValueError(f"unknown algorithm {algorithm!r}")
