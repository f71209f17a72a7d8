"""Device-resident multi-round execution engines (DESIGN.md §8).

The paper amortizes communication by running many cheap local rounds
(L steps × T rounds), but a per-round host loop pays host-side overhead
*every round*: minibatch sampling + H2D transfer, one jit dispatch, a
blocking metrics sync, and a D2H parameter pull for the posterior bank.
This module provides two interchangeable engines:

* :class:`HostRoundEngine` — the per-round dispatch loop, kept as the
  reference oracle (host :class:`~repro.core.posterior.SampleBank`,
  blocking ``float()`` metrics per round).
* :class:`ScanRoundEngine` — fuses ``chunk`` rounds into one jitted
  ``jax.lax.scan`` super-round with donated carry buffers (params/v/v̄ are
  3× model size — no per-chunk copies), on-device minibatch sampling from
  :class:`~repro.data.partition.DeviceShards`, and an on-device
  :class:`~repro.core.posterior.DeviceSampleBank` ring buffer. The host
  sees one dispatch and one small metrics transfer per chunk.
* :class:`ShardRoundEngine` — the SPMD path (DESIGN.md §4/§9): the node
  axis K is *genuinely sharded* over a 1-D mesh axis, the scan-fused
  super-round runs inside ``shard_map`` with donated node-sharded state,
  and the Ω-mixing executes as explicit ``lax.ppermute`` neighbor exchange
  (``repro.core.gossip.make_shard_mixer``). Requires a round function
  built with the matching ``shard_ctx``
  (:func:`repro.core.algorithms.make_round_fn`).

All engines consume the *same* PRNG streams: per round,
``key, kround = jax.random.split(key)`` and the data key is
``fold_in(kround, DATA_STREAM_SALT)``; every per-node stream is derived
from the node's *global* id. Their trajectories (params, metrics,
posterior banks) therefore coincide — bitwise for the shard engine's
per-node state — and the equivalence tests in ``tests/test_engine.py`` /
``tests/test_shard.py`` pin this down.

Where the time goes is visible in a ``jax.profiler`` trace. Inside a chunk
each phase runs under a ``jax.named_scope`` that reaches every op's HLO
``op_name``: ``sample_batches``, the round function's ``local_step``,
``encode``, ``decode``, ``mix``, ``langevin_noise`` and ``round_metrics``
(:mod:`repro.core.algorithms`), and ``bank_admit``. On the host each chunk
of ``run`` is the spans ``engine.dispatch`` (the chunk call),
``engine.sync_metrics`` (the loss and consensus transfer, which waits for
the chunk) and ``engine.histories`` (the other metric columns).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.posterior import DeviceSampleBank, SampleBank
from repro.data.partition import DeviceShards

# Salt folding the round key into the data-sampling stream. Kept separate
# from the kql/knoise/kmix derivations inside the round functions so adding
# on-device sampling does not perturb the algorithm streams.
DATA_STREAM_SALT = 7


def round_data_key(kround: jax.Array) -> jax.Array:
    """Data-sampling key for one round, derived from the round key."""
    return jax.random.fold_in(kround, DATA_STREAM_SALT)


class EngineCarry(NamedTuple):
    """Donated scan carry (state, key, round index) — a pure value threaded through compiled super-rounds."""
    state: Any                    # FedState
    key: jax.Array                # trainer-level PRNG stream
    bank: Any                     # DeviceBankState or None


class ChunkMetrics(NamedTuple):
    """Per-round scalars, reduced on device (one small D2H per chunk).

    Deterministic device-side reductions; no host RNG touches them.
    """
    loss: jax.Array               # (chunk,) mean over (K, L)
    consensus: jax.Array          # (chunk,)
    delta_norm: jax.Array         # (chunk,)
    wire: jax.Array               # (chunk,) measured bytes/node/round
    cross: jax.Array              # (chunk,) cross-shard bytes/node/round
    # lossy-transport columns (all 0 when no transport is configured):
    offered: jax.Array            # (chunk,) on-air bytes/node/round offered
    delivered: jax.Array          # (chunk,) bytes/node/round delivered
    airtime: jax.Array            # (chunk,) TX airtime s/node/round
    energy: jax.Array             # (chunk,) TX energy J/node/round
    # reliability / barrier-free columns (0 / 1 when not configured):
    retransmits: jax.Array        # (chunk,) ARQ frame re-sends/node/round
    abandoned: jax.Array          # (chunk,) bytes/node/round abandoned
    participation: jax.Array      # (chunk, K) per-node round participation
                                  # ((chunk,) scalars when no model is set)


LogCb = Callable[[int, float, float], None]

# (engine attribute, ChunkMetrics field, RoundMetrics field) for the
# per-round histories every engine exposes after run() (the trainer
# collects them by the attribute names)
_HISTORY_FIELDS = (
    ("last_wire_history", "wire", "wire_bytes"),
    ("last_cross_history", "cross", "cross_bytes"),
    ("last_offered_history", "offered", "offered_bytes"),
    ("last_delivered_history", "delivered", "delivered_bytes"),
    ("last_airtime_history", "airtime", "airtime_s"),
    ("last_energy_history", "energy", "energy_j"),
    ("last_retransmit_history", "retransmits", "retransmits"),
    ("last_abandoned_history", "abandoned", "abandoned_bytes"),
    ("last_participation_history", "participation", "participation"),
)


def _chunk_metrics(metrics, loss) -> ChunkMetrics:
    """One round's ChunkMetrics row from its RoundMetrics and mean loss."""
    with jax.named_scope("round_metrics"):
        return ChunkMetrics(
            loss=loss,
            consensus=metrics.consensus_error,
            delta_norm=metrics.delta_norm,
            wire=metrics.wire_bytes,
            cross=jnp.float32(metrics.cross_bytes),
            offered=jnp.float32(metrics.offered_bytes),
            delivered=jnp.float32(metrics.delivered_bytes),
            airtime=jnp.float32(metrics.airtime_s),
            energy=jnp.float32(metrics.energy_j),
            retransmits=jnp.float32(metrics.retransmits),
            abandoned=jnp.float32(metrics.abandoned_bytes),
            participation=jnp.asarray(metrics.participation, jnp.float32),
        )


def _round_in_chunk(engine, data, sizes, state, bank, t, kround,
                    node_ids=None):
    """One round inside a chunk: sample the minibatches on the device, run
    the round function, admit the params to the bank."""
    shards_now = DeviceShards(data=data, sizes=sizes,
                              example_field=engine.shards.example_field)
    with jax.named_scope("sample_batches"):
        batches = shards_now.sample(round_data_key(kround),
                                    engine.local_steps, engine.minibatch,
                                    node_ids=node_ids)
    state, metrics = engine.round_fn(state, batches, kround)
    if engine.bank is not None:
        with jax.named_scope("bank_admit"):
            bank = engine.bank.update(bank, t, state.params)
    return state, bank, metrics


def _collect(ms: ChunkMetrics, losses: List[float], cons: List[float],
             hists: dict) -> None:
    """A chunk's metrics to the host. The loss and consensus transfer waits
    for the chunk; the other columns follow."""
    with TraceAnnotation("engine.sync_metrics"):
        losses.extend(np.asarray(ms.loss, np.float64).tolist())
        cons.extend(np.asarray(ms.consensus, np.float64).tolist())
    with TraceAnnotation("engine.histories"):
        _extend_histories(hists, ms)


def _init_histories(engine) -> None:
    for attr, _, _ in _HISTORY_FIELDS:
        setattr(engine, attr, [])


def _reset_histories(engine) -> dict:
    """Fresh per-run history lists, installed on the engine and returned
    keyed by ChunkMetrics field name for the run loop to extend."""
    out = {}
    for attr, field, _ in _HISTORY_FIELDS:
        lst: List[float] = []
        setattr(engine, attr, lst)
        out[field] = lst
    return out


def _extend_histories(hists: dict, ms: ChunkMetrics) -> None:
    """Append one entry per round: floats for scalar columns, a K-list per
    round for the participation vector (``tolist`` handles both ranks)."""
    for field, lst in hists.items():
        lst.extend(np.asarray(getattr(ms, field), np.float64).tolist())


def _append_round_histories(hists: dict, metrics) -> None:
    """Host-loop variant of :func:`_extend_histories`: one RoundMetrics."""
    for _, field, rfield in _HISTORY_FIELDS:
        hists[field].append(
            np.asarray(getattr(metrics, rfield), np.float64).tolist())


def _check_same_layout(old: DeviceShards, new: DeviceShards) -> None:
    """Swapped shards must keep the compiled layout (shapes/dtypes/field):
    a mismatch would silently retrace every cached chunk fn."""
    if new.example_field != old.example_field:
        raise ValueError(f"set_shards: example_field changed "
                         f"({old.example_field!r} -> {new.example_field!r})")
    old_l = {f: (v.shape, v.dtype) for f, v in old.data.items()}
    new_l = {f: (v.shape, v.dtype) for f, v in new.data.items()}
    if old_l != new_l:
        raise ValueError(f"set_shards: data layout changed "
                         f"({old_l} -> {new_l})")


class ScanRoundEngine:
    """R federated rounds as chunked, donated ``lax.scan`` super-rounds.

    The node shards enter every chunk as explicit jit arguments (not
    trace-time closure constants), so :meth:`set_shards` — the streaming
    drift hook — swaps the training distribution between chunks without
    invalidating a single compiled chunk fn (same shapes, zero recompiles).

    Bitwise-equivalent to :class:`HostRoundEngine` round-for-round (tier-1 gated).
    """

    name = "scan"

    def __init__(self, round_fn, shards: DeviceShards, local_steps: int,
                 minibatch: int, bank: Optional[DeviceSampleBank] = None,
                 default_chunk: int = 64):
        self.round_fn = round_fn          # un-jitted: traced into the scan
        self.shards = shards
        self.local_steps = int(local_steps)
        self.minibatch = int(minibatch)
        self.bank = bank
        self.default_chunk = int(default_chunk)
        self._chunk_fns = {}              # static chunk length -> compiled fn
        _init_histories(self)

    def set_shards(self, shards: DeviceShards) -> None:
        """Swap the training data between chunks (drift refresh). The new
        shards must match the current layout bit-for-bit in shape/dtype."""
        _check_same_layout(self.shards, shards)
        self.shards = shards

    # -- one round, traced inside the scan --------------------------------
    def _body(self, data, sizes, carry: EngineCarry, t
              ) -> Tuple[EngineCarry, ChunkMetrics]:
        state, key, bank = carry
        key, kround = jax.random.split(key)
        state, bank, metrics = _round_in_chunk(self, data, sizes, state,
                                               bank, t, kround)
        with jax.named_scope("round_metrics"):
            loss = jnp.mean(metrics.loss)
        return EngineCarry(state, key, bank), _chunk_metrics(metrics, loss)

    def _chunk_fn(self, length: int):
        if length not in self._chunk_fns:
            def chunk(data_sizes, carry, t0):
                data, sizes = data_sizes
                ts = t0 + jnp.arange(length, dtype=jnp.int32)
                return jax.lax.scan(partial(self._body, data, sizes),
                                    carry, ts)

            # donate the carry: params/v/v_bar (+ bank slots) update in place
            self._chunk_fns[length] = jax.jit(chunk, donate_argnums=(1,))
        return self._chunk_fns[length]

    def lower_chunk(self, state, key, bank_state, rounds: int, t0: int = 0):
        """The ``rounds``-round chunk program for these arguments, lowered
        and not run (``.compile().as_text()`` gives the optimized HLO, each
        op's round scope in its ``op_name`` metadata)."""
        return self._chunk_fn(rounds).lower(
            (self.shards.data, self.shards.sizes),
            EngineCarry(state, key, bank_state), jnp.asarray(t0, jnp.int32))

    def run(self, state, key, bank_state, rounds: int, t0: int = 0,
            log_every: int = 0, log_cb: Optional[LogCb] = None):
        """Run ``rounds`` rounds from global round index ``t0``.

        Chunk sizes align with ``log_every`` so streaming logs keep their
        cadence; without logging, ``default_chunk``-sized super-rounds.
        Returns ``(state, key, bank_state, losses, consensus)`` with the
        per-round scalar histories as host floats; the measured per-round
        wire bytes land in :attr:`last_wire_history` (same length).
        """
        carry = EngineCarry(state, key, bank_state)
        chunk = log_every if log_every > 0 else min(rounds, self.default_chunk)
        losses: List[float] = []
        cons: List[float] = []
        hists = _reset_histories(self)
        done = 0
        while done < rounds:
            n = min(chunk, rounds - done)
            data_sizes = (self.shards.data, self.shards.sizes)
            with TraceAnnotation("engine.dispatch"):
                carry, ms = self._chunk_fn(n)(
                    data_sizes, carry, jnp.asarray(t0 + done, jnp.int32))
            _collect(ms, losses, cons, hists)
            done += n
            # same cadence as the host loop: only exact log_every multiples
            # (a non-aligned remainder chunk does not emit a log line)
            if log_cb is not None and log_every and done % log_every == 0:
                log_cb(t0 + done, losses[-1], cons[-1])
        return carry.state, carry.key, carry.bank, losses, cons


class HostRoundEngine:
    """Per-round dispatch loop — the original harness, kept as the oracle.

    Intentionally preserves the host-side costs the scan engine removes:
    one jit dispatch per round, a blocking ``float()`` metrics sync, and a
    D2H parameter pull into the host :class:`SampleBank` for every admitted
    posterior sample. ``bank_state`` is a (mutable) :class:`SampleBank`.

    Deterministic given ``(state, key)`` — the bitwise reference the other engines are gated against.
    """

    name = "host"

    def __init__(self, round_fn, shards: DeviceShards, local_steps: int,
                 minibatch: int, bank: Optional[DeviceSampleBank] = None):
        self.round_fn = jax.jit(round_fn)
        self.shards = shards
        self.local_steps = int(local_steps)
        self.minibatch = int(minibatch)
        self.bank = bank                  # config only: burn_in/thin/capacity
        _init_histories(self)

    def set_shards(self, shards: DeviceShards) -> None:
        """Swap the training data (drift refresh); layout must match."""
        _check_same_layout(self.shards, shards)
        self.shards = shards

    def make_bank(self) -> Optional[SampleBank]:
        if self.bank is None:
            return None
        return SampleBank(burn_in=self.bank.burn_in,
                          max_samples=self.bank.capacity,
                          thin=self.bank.thin)

    def run(self, state, key, bank_state, rounds: int, t0: int = 0,
            log_every: int = 0, log_cb: Optional[LogCb] = None):
        losses: List[float] = []
        cons: List[float] = []
        hists = _reset_histories(self)
        for i in range(rounds):
            t = t0 + i
            key, kround = jax.random.split(key)
            batches = self.shards.sample(round_data_key(kround),
                                         self.local_steps, self.minibatch)
            state, metrics = self.round_fn(state, batches, kround)
            losses.append(float(jnp.mean(metrics.loss)))
            cons.append(float(metrics.consensus_error))
            _append_round_histories(hists, metrics)
            if self.bank is not None and bank_state is not None:
                # same admit rule as DeviceSampleBank.admit_mask for rounds
                # visited sequentially: t >= burn_in, (t - burn_in) % thin == 0
                bank_state.maybe_add(t, state.params)
            if log_cb is not None and log_every and (i + 1) % log_every == 0:
                log_cb(t + 1, losses[-1], cons[-1])
        return state, key, bank_state, losses, cons


class ShardRoundEngine:
    """Scan-fused super-rounds with the node axis sharded over a mesh axis.

    The chunked ``lax.scan`` runs *inside* ``shard_map``: every program
    instance owns K/S nodes' params/v/v̄ rows, posterior-bank slots and
    data shards, and the Ω-mixing inside the round function is explicit
    ``lax.ppermute`` neighbor exchange. The carry is donated, so sharded
    state updates in place; per-round metrics are psum-reduced on device.

    ``round_fn`` MUST be built with the matching ``shard_ctx``
    (``make_round_fn(..., shard_ctx=ShardContext(fed_axis, S))``) — it is
    traced on shard-local rows and uses the mesh axis by name. Because
    every per-node PRNG stream keys off the node's global id, the
    trajectory is bitwise identical per node to :class:`HostRoundEngine`
    running the same-config unsharded round function.
    """

    name = "shard"

    def __init__(self, round_fn, shards: DeviceShards, local_steps: int,
                 minibatch: int, bank: Optional[DeviceSampleBank] = None,
                 default_chunk: int = 64, mesh=None, fed_axis: str = "fed"):
        if mesh is None:
            from repro.launch.mesh import make_fed_mesh
            mesh = make_fed_mesh(fed_axis=fed_axis)
        self.mesh = mesh
        self.fed_axis = fed_axis
        self.num_shards = int(mesh.shape[fed_axis])
        if shards.num_nodes % self.num_shards:
            raise ValueError(
                f"K={shards.num_nodes} nodes not divisible by "
                f"{self.num_shards} shards on axis {fed_axis!r}")
        self.round_fn = round_fn          # shard_ctx-built, un-jitted
        self.shards = shards.with_sharding(mesh, fed_axis)
        self.local_steps = int(local_steps)
        self.minibatch = int(minibatch)
        self.bank = bank
        self.default_chunk = int(default_chunk)
        self._chunk_fns = {}
        _init_histories(self)

    def set_shards(self, shards: DeviceShards) -> None:
        """Swap the training data (drift refresh): re-placed on the fed
        mesh; layout must match the compiled chunk fns bit-for-bit."""
        _check_same_layout(self.shards, shards)
        self.shards = shards.with_sharding(self.mesh, self.fed_axis)

    # -- spec/placement helpers -------------------------------------------
    def _carry_specs(self, carry: EngineCarry):
        """shard_map boundary specs for the carry, built from the shared
        spec sources (launch.sharding.fed_state_pspecs for the FedState,
        DeviceSampleBank.pspecs for the bank) so 'which leaves are
        node-sharded' lives in exactly one place per container."""
        from repro.launch.sharding import fed_state_pspecs
        state, _key, bank = carry
        bank_specs = (self.bank.pspecs(bank, self.fed_axis)
                      if bank is not None else None)
        return EngineCarry(fed_state_pspecs(state, self.fed_axis), P(),
                           bank_specs)

    def place(self, carry: EngineCarry) -> EngineCarry:
        """device_put the carry onto the fed mesh (node axes sharded)."""
        specs = self._carry_specs(carry)
        shardings = jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)
        return jax.device_put(carry, shardings)

    # -- one round on this shard's nodes, traced inside the scan ----------
    def _body(self, data, sizes, carry: EngineCarry, t):
        state, key, bank = carry
        key, kround = jax.random.split(key)
        local_k = state.key.shape[0]
        r = jax.lax.axis_index(self.fed_axis)
        ids = r * local_k + jnp.arange(local_k, dtype=jnp.int32)
        state, bank, metrics = _round_in_chunk(self, data, sizes, state,
                                               bank, t, kround, node_ids=ids)
        # loss is shard-local (lk, L); psum for the global per-round mean.
        # consensus/delta_norm/wire/cross come out of the round fn already
        # globally reduced (psum) or shard-invariant (static byte counts);
        # the participation vector derives from the replicated round key,
        # so it is identical on every shard — a replicated out_spec.
        n_total = metrics.loss.size * self.num_shards
        with jax.named_scope("round_metrics"):
            loss_mean = jax.lax.psum(
                jnp.sum(metrics.loss.astype(jnp.float32)), self.fed_axis
            ) / n_total
        return EngineCarry(state, key, bank), _chunk_metrics(metrics,
                                                             loss_mean)

    def _chunk_fn(self, length: int, carry: EngineCarry):
        if length not in self._chunk_fns:
            carry_specs = self._carry_specs(carry)
            data_specs = (jax.tree.map(lambda _: P(self.fed_axis),
                                       self.shards.data), P(self.fed_axis))
            metric_specs = ChunkMetrics(*([P()] * len(ChunkMetrics._fields)))

            def local_chunk(data_sizes, carry, t0):
                data, sizes = data_sizes
                ts = t0 + jnp.arange(length, dtype=jnp.int32)
                return jax.lax.scan(partial(self._body, data, sizes),
                                    carry, ts)

            def chunk(data_sizes, carry, t0):
                return jax.shard_map(
                    local_chunk, mesh=self.mesh,
                    in_specs=(data_specs, carry_specs, P()),
                    out_specs=(carry_specs, metric_specs), check_vma=False,
                )(data_sizes, carry, t0)

            self._chunk_fns[length] = jax.jit(chunk, donate_argnums=(1,))
        return self._chunk_fns[length]

    def run(self, state, key, bank_state, rounds: int, t0: int = 0,
            log_every: int = 0, log_cb: Optional[LogCb] = None):
        """Same contract as :meth:`ScanRoundEngine.run`, node axis sharded."""
        carry = self.place(EngineCarry(state, key, bank_state))
        data_sizes = (self.shards.data, self.shards.sizes)
        chunk = log_every if log_every > 0 else min(rounds, self.default_chunk)
        losses: List[float] = []
        cons: List[float] = []
        hists = _reset_histories(self)
        done = 0
        while done < rounds:
            n = min(chunk, rounds - done)
            with TraceAnnotation("engine.dispatch"):
                carry, ms = self._chunk_fn(n, carry)(
                    data_sizes, carry, jnp.asarray(t0 + done, jnp.int32))
            _collect(ms, losses, cons, hists)
            done += n
            if log_cb is not None and log_every and done % log_every == 0:
                log_cb(t0 + done, losses[-1], cons[-1])
        return carry.state, carry.key, carry.bank, losses, cons


def make_engine(name: str, round_fn, shards: DeviceShards, local_steps: int,
                minibatch: int, bank: Optional[DeviceSampleBank] = None,
                chunk: int = 64, mesh=None, fed_axis: str = "fed"):
    """Engine factory: ``"scan"`` (default, fused), ``"host"`` (oracle), or
    ``"shard"`` (SPMD: node axis sharded over ``mesh``'s ``fed_axis``,
    requires a ``shard_ctx``-built round function)."""
    if name == "scan":
        return ScanRoundEngine(round_fn, shards, local_steps, minibatch,
                               bank=bank, default_chunk=chunk)
    if name == "host":
        return HostRoundEngine(round_fn, shards, local_steps, minibatch,
                               bank=bank)
    if name == "shard":
        return ShardRoundEngine(round_fn, shards, local_steps, minibatch,
                                bank=bank, default_chunk=chunk, mesh=mesh,
                                fed_axis=fed_axis)
    raise ValueError(f"unknown engine {name!r}; use 'scan', 'host' or 'shard'")
