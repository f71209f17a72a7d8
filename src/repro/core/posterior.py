"""Posterior sample bank + Bayesian model averaging.

Gradient-based MCMC (SGLD family) treats post burn-in iterates as samples
from p(θ|D). We keep a bounded reservoir of samples (thinned) and predict by
averaging the *probabilities* (not logits) across samples — the standard BMA
predictive distribution that gives the calibration gains the paper measures.
"""
from __future__ import annotations

import contextvars
import warnings
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class SampleBank:
    """Host-side reservoir of posterior samples (thinned, post burn-in).

    Mutable host state — the reference oracle for :class:`DeviceSampleBank`
    (admission/eviction semantics are pinned equal by tests/test_engine.py).

    Admission is deterministic in ``(round, burn_in, thin, capacity)`` and slots store exact chain bits — replaying a run refills an identical bank.
    """

    def __init__(self, burn_in: int, max_samples: int = 50, thin: int = 1):
        self.burn_in = burn_in
        self.max_samples = max_samples
        self.thin = thin
        self.samples: List[Any] = []
        self.rounds: List[int] = []   # admission round per sample (aging)
        self._seen = 0

    def maybe_add(self, round_idx: int, params) -> bool:
        if round_idx < self.burn_in:
            return False
        self._seen += 1
        if (self._seen - 1) % self.thin != 0:
            return False
        params = jax.tree.map(np.asarray, params)
        if len(self.samples) >= self.max_samples:
            # reservoir-style: drop the oldest (keeps a moving posterior window,
            # which also tracks the paper's continual daily re-training)
            self.samples.pop(0)
            self.rounds.pop(0)
        self.samples.append(params)
        self.rounds.append(int(round_idx))
        return True

    def __len__(self):
        return len(self.samples)


class DeviceBankState(NamedTuple):
    """Scan-carried ring buffer of posterior samples (DESIGN.md §8).

    ``slots`` mirrors the params pytree with a leading capacity axis
    ``(C, ...)``; ``count`` is the number of samples ever admitted (the
    write pointer is ``count % C``, so eviction drops the oldest — exactly
    the host :class:`SampleBank`'s pop-front behavior). Under the int8
    storage mode ``slots`` holds the quantized grid and ``scales`` the
    per-(slot, row) f32 dequantization scales; ``None`` (an empty pytree)
    in the default f32 mode, so the state stays scan/donation compatible
    either way.

    ``rounds`` records the admission round per slot (``-1`` = empty), the
    raw material for the continual-learning age weights (DESIGN.md §15);
    it rides along replicated and costs ``C`` int32s.
    """
    slots: Any           # leaves (C, ...) — params with capacity axis
    count: jax.Array     # scalar int32, total samples admitted
    scales: Any = None   # int8 mode: f32 leaves (C, *leaf.shape[:1])
    rounds: Any = None   # (C,) int32 admission round per slot, -1 empty


class DeviceSampleBank:
    """On-device fixed-capacity posterior bank, pure and scan-safe.

    Matches :class:`SampleBank` semantics bit-for-bit: a round ``t`` is
    admitted iff ``t >= burn_in`` and ``(t - burn_in) % thin == 0``; once
    full, the oldest sample is evicted. The admit decision is realized with
    ``lax.select`` on the round counter, so update cost is one slot write
    per round regardless of the branch taken (donation keeps it in place).

    ``store_dtype="int8"`` stores each admitted sample as a symmetric
    absmax-quantized int8 grid with per-(slot, leading-row) f32 scales —
    4× less device memory per slot, so a multi-sample posterior fits
    on-device at 100M+ params (ROADMAP item 5). The leading row axis of a
    leaf is the node axis under the trainer's layout, so the scales shard
    over ``fed_axis`` exactly like the slots and quantization stays a
    node-local op. The f32 default path is bitwise-untouched.
    """

    def __init__(self, burn_in: int, capacity: int = 40, thin: int = 1,
                 store_dtype: str = "float32"):
        self.burn_in = int(burn_in)
        self.capacity = int(capacity)
        self.thin = max(1, int(thin))
        self.store_dtype = str(store_dtype)
        if self.store_dtype not in ("float32", "int8"):
            raise ValueError(f"store_dtype must be float32|int8, "
                             f"got {store_dtype!r}")

    def init(self, params) -> DeviceBankState:
        rounds = jnp.full((self.capacity,), -1, jnp.int32)
        if self.store_dtype == "int8":
            slots = jax.tree.map(
                lambda x: jnp.zeros((self.capacity,) + x.shape, jnp.int8),
                params,
            )
            scales = jax.tree.map(
                lambda x: jnp.ones((self.capacity,) + x.shape[:1],
                                   jnp.float32),
                params,
            )
            return DeviceBankState(slots=slots,
                                   count=jnp.zeros((), jnp.int32),
                                   scales=scales, rounds=rounds)
        slots = jax.tree.map(
            lambda x: jnp.zeros((self.capacity,) + x.shape, jnp.float32),
            params,
        )
        return DeviceBankState(slots=slots, count=jnp.zeros((), jnp.int32),
                               rounds=rounds)

    # -- int8 storage helpers ---------------------------------------------
    @staticmethod
    def _leaf_scale(x) -> jnp.ndarray:
        """Per-leading-row absmax/127 scale (node-local under the trainer
        layout); 1.0 where the row is all zero, so dequant stays exact."""
        x32 = x.astype(jnp.float32)
        red = tuple(range(1, x32.ndim))
        amax = jnp.max(jnp.abs(x32), axis=red) if red else jnp.abs(x32)
        return jnp.where(amax > 0, amax / 127.0, 1.0)

    @classmethod
    def _quantize_leaf(cls, x) -> jnp.ndarray:
        scale = cls._leaf_scale(x)
        x32 = x.astype(jnp.float32)
        s = scale.reshape(scale.shape + (1,) * (x32.ndim - scale.ndim))
        q = jnp.round(x32 / s)
        return jnp.clip(q, -127, 127).astype(jnp.int8)

    def admit_mask(self, round_idx) -> jax.Array:
        """Whether round ``round_idx``'s params enter the bank (traceable)."""
        since = round_idx - self.burn_in
        return jnp.logical_and(since >= 0, since % self.thin == 0)

    def update(self, bank: DeviceBankState, round_idx, params
               ) -> DeviceBankState:
        """Pure ring-buffer write, jit/scan-safe (round_idx may be traced)."""
        add = self.admit_mask(round_idx)
        ptr = jnp.mod(bank.count, self.capacity)

        def write(slot_leaf, p_leaf):
            cur = jax.lax.dynamic_index_in_dim(slot_leaf, ptr, 0,
                                               keepdims=False)
            new = jax.lax.select(
                add, p_leaf.astype(slot_leaf.dtype), cur
            )
            return jax.lax.dynamic_update_index_in_dim(slot_leaf, new, ptr, 0)

        rounds = bank.rounds
        if rounds is not None:
            cur_r = jax.lax.dynamic_index_in_dim(rounds, ptr, 0,
                                                 keepdims=False)
            new_r = jax.lax.select(add, jnp.asarray(round_idx, jnp.int32),
                                   cur_r)
            rounds = jax.lax.dynamic_update_index_in_dim(rounds, new_r,
                                                         ptr, 0)
        if bank.scales is not None:
            qtree = jax.tree.map(self._quantize_leaf, params)
            stree = jax.tree.map(self._leaf_scale, params)
            return DeviceBankState(
                slots=jax.tree.map(write, bank.slots, qtree),
                count=bank.count + add.astype(jnp.int32),
                scales=jax.tree.map(write, bank.scales, stree),
                rounds=rounds)
        slots = jax.tree.map(write, bank.slots, params)
        return DeviceBankState(slots=slots,
                               count=bank.count + add.astype(jnp.int32),
                               rounds=rounds)

    # -- mesh placement ---------------------------------------------------
    def pspecs(self, bank: DeviceBankState, fed_axis: str) -> DeviceBankState:
        """PartitionSpec tree: the node axis of every slot leaf (dim 1,
        after capacity) shards over ``fed_axis``, the admit counter stays
        replicated. Under the shard engine each mesh slice then holds only
        its own nodes' posterior chains; the engine consumes these specs
        for its ``shard_map`` boundary and initial placement."""
        from jax.sharding import PartitionSpec as P
        return DeviceBankState(
            slots=jax.tree.map(lambda _: P(None, fed_axis), bank.slots),
            count=P(),
            scales=(None if bank.scales is None else jax.tree.map(
                lambda s: P(None, fed_axis) if s.ndim > 1 else P(None),
                bank.scales)),
            rounds=(None if bank.rounds is None else P(None)),
        )

    # -- host-side views -------------------------------------------------
    def order(self, bank: DeviceBankState) -> np.ndarray:
        """Slot indices oldest→newest (the host bank's list order)."""
        count = int(bank.count)
        if count <= self.capacity:
            return np.arange(count)
        ptr = count % self.capacity
        return (ptr + np.arange(self.capacity)) % self.capacity

    def stacked(self, bank: DeviceBankState):
        """(S, ...) stacked samples in insertion order (S = len(bank)),
        dequantized to f32 under the int8 storage mode."""
        order = jnp.asarray(self.order(bank))
        if bank.scales is not None:
            def deq(s, sc):
                rows = s[order].astype(jnp.float32)
                scr = sc[order]
                return rows * scr.reshape(
                    scr.shape + (1,) * (rows.ndim - scr.ndim))
            return jax.tree.map(deq, bank.slots, bank.scales)
        return jax.tree.map(lambda s: s[order], bank.slots)

    def samples_list(self, bank: DeviceBankState) -> List[Any]:
        """Materialize as the host SampleBank's list-of-pytrees view."""
        stacked = jax.tree.map(np.asarray, self.stacked(bank))
        n = len(self.order(bank))
        return [jax.tree.map(lambda s: s[i], stacked) for i in range(n)]

    def length(self, bank: DeviceBankState) -> int:
        return min(int(bank.count), self.capacity)

    def rounds_list(self, bank: DeviceBankState) -> np.ndarray:
        """Admission rounds in insertion order (host SampleBank.rounds)."""
        if bank.rounds is None:
            return np.zeros((self.length(bank),), np.int32)
        return np.asarray(bank.rounds)[self.order(bank)]

    def age_weights(self, bank: DeviceBankState, now: int,
                    window: int = 0, decay: float = 1.0) -> np.ndarray:
        """Age-discounted BMA weights in insertion order (DESIGN.md §15)."""
        return bank_age_weights(self.rounds_list(bank), now,
                                window=window, decay=decay)


def bank_age_weights(rounds, now: int, window: int = 0,
                     decay: float = 1.0) -> np.ndarray:
    """Age-discounted, window-evicted BMA weights over a sample bank.

    Pure host function of ``(rounds, now, window, decay)``: sample ``i``
    with admission round ``r_i`` gets raw weight ``decay ** (now - r_i)``,
    zeroed when ``window > 0`` and ``now - r_i >= window`` (hard eviction
    from the predictive mixture without touching device slots), then
    renormalized to sum to one. Invariants pinned by tests/test_drift.py:
    weights are non-negative, sum to 1, and are non-increasing with age.
    If every sample falls outside the window, the newest sample alone
    carries weight 1 — the predictor never divides by zero and always has
    at least one vote.
    """
    rounds = np.asarray(rounds, np.int64)
    if rounds.size == 0:
        return np.zeros((0,), np.float64)
    age = np.maximum(np.int64(now) - rounds, 0)
    w = np.power(np.float64(min(max(decay, 0.0), 1.0)), age)
    if window > 0:
        w = np.where(age < window, w, 0.0)
    total = float(w.sum())
    if total <= 0.0:
        w = np.zeros_like(w)
        w[int(np.argmin(age))] = 1.0
        return w
    return w / total


# Set while a BankPredictor traces its program: a model's batching rule
# appends to it when it runs the bank's members as one packed forward.
_PACKED_FORWARDS: contextvars.ContextVar = contextvars.ContextVar(
    "packed_forwards", default=None)


def note_packed_forward() -> None:
    """Record, at trace time, that a model's batching rule ran the members
    of the bank being traced as one packed forward (e.g. the LeNet conv
    tower with the member axis in the channels)."""
    seen = _PACKED_FORWARDS.get()
    if seen is not None:
        seen.append(True)


def bma_predict_stacked(apply_fn: Callable, stacked, batch,
                        node_axis: Optional[int] = None,
                        weights=None) -> jnp.ndarray:
    """BMA over a stacked ``(S, ...)`` sample axis in one traced vmap.

    Same predictive distribution as :func:`bma_predict` over the equivalent
    list of samples, but the sample loop is a ``vmap`` instead of S traced
    calls — one dispatch for the whole bank (and one XLA program to fuse).

    ``weights`` (optional, shape ``(S,)``) replaces the uniform sample mean
    with an age-discounted mixture (:func:`bank_age_weights`); nodes are
    still averaged uniformly first. The ``weights=None`` path is bitwise
    identical to the pre-continual kernel — weighted averaging is a
    separate reduction, never a rescaled default path.
    """
    if node_axis is not None:
        per_sample = lambda p: jax.vmap(lambda q: apply_fn(q, batch))(p)
    else:
        per_sample = lambda p: apply_fn(p, batch)
    logits = jax.vmap(per_sample)(stacked)      # (S, [K,] B, classes)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if weights is None:
        axes = (0, 1) if node_axis is not None else (0,)
        return jnp.mean(probs, axis=axes)
    if node_axis is not None:
        probs = jnp.mean(probs, axis=1)         # nodes first, then samples
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), jnp.float32(1e-12))
    return jnp.einsum("s,s...->...", w, probs)


def predictive_entropy(probs: jnp.ndarray) -> jnp.ndarray:
    """Entropy of the predictive distribution, nats, last axis reduced.

    The paper's serving-time uncertainty signal — high entropy means the
    posterior disagrees and the prediction should not be trusted. This is
    the *one* entropy formula: the eval accumulators, the serving engine's
    abstain gate and the CLI all route through it, so an entropy threshold
    tuned on an eval report transfers to serving unchanged.
    """
    p = probs.astype(jnp.float32)
    return -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-12)), axis=-1)


class PosteriorPredictor:
    """The one way to get predictions out of a posterior (DESIGN.md §14).

    ``predict(batch) -> (probs, entropy)`` — BMA probabilities plus the
    predictive-entropy uncertainty signal, whatever holds the samples.
    Eval engines, the serving plane and the examples all consume this
    protocol; the legacy per-sample loops (:func:`bma_predict`, serve.py's
    ad-hoc softmax loop) are deprecated in its favor.

    Deterministic: same samples, same batch, same engine — same probability bits.
    """

    def predict(self, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError


class BankPredictor(PosteriorPredictor):
    """Compiled-once facade over a resident stacked sample bank.

    ``stacked`` carries a leading sample axis ``(S, ...)`` (and with
    ``node_axis=1`` a node-chain axis ``(S, K, ...)``) — the layout
    :meth:`DeviceSampleBank.stacked` produces. The BMA kernel is jitted
    once per batch shape; :meth:`install` atomically swaps in a new bank
    between calls without touching the compiled path (same sample-axis
    shape → zero recompiles, the serving engine's hot-swap contract).

    With ``mesh``/``ensemble_axis`` the sample axis is sharded over the
    mesh (:func:`place_ensemble`), so BMA cost scales down with devices —
    the ensemble dimension is a parallel axis, not a loop.

    ``install(stacked, weights=None)`` keeps the uniform-mean graph bitwise pre-§15; an age-weight vector routes to a separately-jitted weighted branch.

    ``packed_traces`` and ``per_member_traces`` count the traces of the
    predict programs by whether the model ran the bank as one packed
    forward (:func:`note_packed_forward`) or member by member.
    """

    def __init__(self, apply_fn: Callable, stacked: Any = None,
                 node_axis: Optional[int] = None, mesh=None,
                 ensemble_axis: str = ""):
        self.apply_fn = apply_fn
        self.node_axis = node_axis
        self.mesh = mesh
        self.ensemble_axis = ensemble_axis
        self._fn = jax.jit(self._predict)
        self._fn_weighted = jax.jit(self._predict_weighted)
        self._stacked = None
        self._weights = None
        self.packed_traces = 0
        self.per_member_traces = 0
        if stacked is not None:
            self.install(stacked)

    def _bma(self, stacked, batch, weights=None):
        seen = []
        token = _PACKED_FORWARDS.set(seen)
        try:
            probs = bma_predict_stacked(self.apply_fn, stacked, batch,
                                        node_axis=self.node_axis,
                                        weights=weights)
        finally:
            _PACKED_FORWARDS.reset(token)
        if seen:
            self.packed_traces += 1
        else:
            self.per_member_traces += 1
        return probs, predictive_entropy(probs)

    def _predict(self, stacked, batch):
        return self._bma(stacked, batch)

    def _predict_weighted(self, stacked, weights, batch):
        return self._bma(stacked, batch, weights)

    # -- bank lifecycle ----------------------------------------------------
    def install(self, stacked, weights=None) -> None:
        """Atomically install a new bank (posterior hot swap).

        The reference swap is a single Python assignment, so concurrent
        ``predict`` calls see either the old bank or the new one, never a
        mix. Keeping the sample-axis length constant keeps the compiled
        kernel valid (no recompile, no cache realloc downstream).

        ``weights`` (optional ``(S,)``, e.g. :func:`bank_age_weights`)
        switches ``predict`` onto a separately compiled age-weighted BMA
        kernel; ``weights=None`` keeps the original uniform kernel bitwise
        untouched.
        """
        if self.mesh is not None and self.ensemble_axis:
            stacked = place_ensemble(stacked, self.mesh, self.ensemble_axis)
        self._weights = (None if weights is None
                         else jnp.asarray(weights, jnp.float32))
        self._stacked = stacked

    @property
    def stacked(self):
        return self._stacked

    def num_samples(self) -> int:
        if self._stacked is None:
            return 0
        return int(jax.tree.leaves(self._stacked)[0].shape[0])

    def compile_count(self) -> int:
        """Entries in the predict kernel's jit cache (zero-recompile gate)."""
        return self._fn._cache_size() + self._fn_weighted._cache_size()

    def predict(self, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if self._stacked is None:
            raise ValueError("no bank installed; call install(stacked)")
        if self._weights is not None:
            return self._fn_weighted(self._stacked, self._weights, batch)
        return self._fn(self._stacked, batch)


def place_ensemble(stacked, mesh, axis: str):
    """Shard the leading (sample) axis of a stacked bank over ``mesh[axis]``.

    Serving's BMA vmap then runs S/num_devices samples per device and the
    probability mean lowers to one all-reduce — the ensemble dimension is
    the natural serving-scale axis because samples never communicate
    until the final average. The sample count must divide the axis size.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = int(mesh.shape[axis])

    def put(x):
        if x.shape[0] % n:
            raise ValueError(
                f"sample axis {x.shape[0]} does not divide over "
                f"mesh axis {axis!r} ({n} devices)")
        spec = P(*((axis,) + (None,) * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(put, stacked)


def bma_predict(apply_fn: Callable, samples: List[Any], batch,
                node_axis: Optional[int] = None) -> jnp.ndarray:
    """Average softmax probabilities over posterior samples.

    .. deprecated:: PR 9
        One traced dispatch per sample; kept only as the legacy reference
        oracle. Use :class:`BankPredictor` (or the stacked kernel
        :func:`bma_predict_stacked`) — one vmap over the whole bank.

    ``apply_fn(params, batch) -> logits``. If params carry a leading node
    axis (decentralized setting), ``node_axis=0`` additionally averages over
    nodes — each node's chain contributes samples, as in the paper's
    evaluation of the device consensus model.
    """
    warnings.warn(
        "bma_predict (per-sample dispatch loop) is deprecated; use "
        "repro.core.posterior.BankPredictor / bma_predict_stacked",
        DeprecationWarning, stacklevel=2)
    probs = None
    n = 0
    for params in samples:
        if node_axis is not None:
            logits = jax.vmap(lambda p: apply_fn(p, batch))(params)
            p_s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            p_s = jnp.mean(p_s, axis=0)
            n_s = 1
        else:
            logits = apply_fn(params, batch)
            p_s = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            n_s = 1
        probs = p_s if probs is None else probs + p_s
        n += n_s
    if probs is None:
        raise ValueError("empty sample bank")
    return probs / n


def point_predict(apply_fn: Callable, params, batch,
                  node_axis: Optional[int] = None) -> jnp.ndarray:
    """Frequentist prediction (CF-FL baseline): single-point softmax."""
    if node_axis is not None:
        logits = jax.vmap(lambda p: apply_fn(p, batch))(params)
        return jnp.mean(
            jax.nn.softmax(logits.astype(jnp.float32), axis=-1), axis=0
        )
    return jax.nn.softmax(apply_fn(params, batch).astype(jnp.float32), axis=-1)
