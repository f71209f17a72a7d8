"""Uncertainty-aware serving engine (DESIGN.md §14).

The old ``launch/serve.py`` demo dispatched one ``jax.jit`` call per
posterior sample per decode step, kept a Python list of per-sample KV
caches that re-allocated on every bank change, and had no notion of
requests — a fixed batch marched in lockstep. This module replaces it
with a persistent engine built around three invariants:

* **Fixed-shape slot table, zero recompiles.** All compiled paths close
  over static shapes only: ``(slots, ...)`` input/cache tables sized at
  construction, slot indices traced. Requests admit and retire per step
  without ever changing a traced shape, so after warmup the jit caches
  hold exactly one entry per kernel (asserted via ``compile_count``).
* **Resident bank, atomic hot swap.** The stacked posterior ``(M, ...)``
  lives on device and every kernel vmaps over it — BMA is one dispatch
  for the whole bank, and with ``ServeConfig.ensemble_axis`` the sample
  axis shards over a mesh (:func:`repro.core.posterior.place_ensemble`).
  :meth:`install_bank` swaps in a fresh training snapshot with a single
  Python reference assignment between steps: in-flight requests finish
  on the new posterior, completed outputs are untouched, and because
  the sample count is held constant neither the compiled kernels nor
  the slot caches are rebuilt (no recompile, no realloc, no leak).
* **Entropy-gated selective prediction.** Every response carries BMA
  probabilities plus predictive entropy; requests whose entropy exceeds
  ``ServeConfig.entropy_threshold`` are flagged ``abstain=True`` —
  route-to-human, the paper's serving-time reliability contract. The
  gate is :func:`repro.eval.engine.abstain_mask`, the same rule the
  eval accumulators use, so thresholds tuned offline transfer exactly.

Two concrete engines share the queue/slot machinery:

* :class:`ClassifyEngine` — single-step requests (radar/CSI sensing
  classifiers). The predict path is a :class:`BankPredictor` over the
  slot table, i.e. literally the eval engines' ``bma_predict_stacked``
  kernel — BMA probabilities are bitwise-equal to an eval pass over the
  same bank at the same batch shape.
* :class:`DecodeEngine` — autoregressive requests with continuous
  batching: per-step admit/retire against ``(M, slots, 1, ...)`` KV
  lanes (an outer vmap over posterior samples, an inner vmap over B=1
  decode lanes), per-slot positions and PRNG streams, BMA-averaged
  next-token distributions sampled per lane.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.config import ServeConfig
from repro.core.posterior import (BankPredictor, place_ensemble,
                                  predictive_entropy)
from repro.eval.engine import abstain_mask


def live_device_bytes() -> int:
    """Bytes held by all live device arrays in the process.

    The steady-state memory gate: N posterior hot swaps must leave this
    flat (old bank freed, caches reused), which is exactly what the old
    serve demo's per-sample cache list violated.
    """
    return int(sum(a.nbytes for a in jax.live_arrays()))


@dataclasses.dataclass
class ServeRequest:
    """One inference request.

    Classify engines read ``x`` (a single example, no batch axis).
    Decode engines read ``prompt_token`` / ``max_new_tokens`` / ``seed``
    (per-request sampling stream — results are reproducible and
    independent of what other requests share the batch).
    """
    x: Any = None
    prompt_token: int = 0
    max_new_tokens: int = 0        # 0 -> ServeConfig.max_new_tokens
    seed: int = 0


@dataclasses.dataclass
class ServeResponse:
    """The API response: prediction + uncertainty + the abstain gate.

    Deterministic given the installed bank and the request bits. The
    timing fields place the request on the engine's step counter:
    ``admit_step`` and ``done_step`` are the ``step`` arguments of the
    ``serve.step`` trace spans that admitted and finished it.
    """
    request_id: int
    probs: np.ndarray              # (C,) BMA predictive distribution
    entropy: float                 # nats; decode: mean over emitted tokens
    abstain: bool                  # entropy gate: route to a human
    bank_version: int              # posterior snapshot that finished this
    latency_s: float               # submit to retire
    queue_s: float                 # submit to admit into a slot
    queued_steps: int              # steps run between submit and admit
    admit_step: int                # index of the step that admitted it
    done_step: int                 # index of the step that finished it
    tokens: Optional[np.ndarray] = None          # decode: (T,) int32
    token_entropy: Optional[np.ndarray] = None   # decode: (T,) f32


class _LatencyHistogram:
    """Latency percentiles from a fixed set of log-spaced buckets (16 per
    doubling from 1 us, so a percentile is read to about 2%): the memory
    stays the same however many requests the engine serves."""

    EDGES = 1e-6 * 2.0 ** (np.arange(16 * 34) / 16.0)

    def __init__(self):
        self.counts = np.zeros(len(self.EDGES) + 1, np.int64)

    def add(self, seconds: float) -> None:
        self.counts[np.searchsorted(self.EDGES, seconds)] += 1

    def percentile(self, q: float) -> float:
        """The ``q``-th percentile in seconds (geometric bucket middle)."""
        n = int(self.counts.sum())
        if not n:
            return 0.0
        i = int(np.searchsorted(np.cumsum(self.counts), q / 100.0 * n))
        lo = self.EDGES[max(i - 1, 0)]
        hi = self.EDGES[min(i, len(self.EDGES) - 1)]
        return float(np.sqrt(lo * hi))


class ServingEngine:
    """Queue + slot-table bookkeeping shared by both concrete engines.

    The loop is host-driven: ``submit`` enqueues, each ``step`` admits
    queued requests into free slots, runs one compiled kernel over the
    whole table, and retires finished slots into responses. ``drain``
    steps until idle; ``run`` is submit-all-then-drain.

    Each step is a ``serve.step`` span in a running ``jax.profiler`` trace
    (arguments: the step index and the slots it runs occupied), split into
    ``serve.admit``, ``serve.predict``, ``serve.fetch`` and
    ``serve.retire``; without a profiler session the spans cost nothing.
    ``steps`` counts the steps that ran a kernel and ``slot_steps`` the
    occupied slots summed over them; ``stats()`` gives the share of slots
    occupied, the share of served requests that waited at least one step
    for a slot, and their mean submit-to-admit time.

    Purity: the classification path reproduces the eval engine's probabilities bitwise (``serve_vs_eval_bitwise``, exact-gated in ``bench_serve``).
    """

    def __init__(self, cfg: ServeConfig):
        self.cfg = cfg
        self.queue: Deque[Tuple[int, ServeRequest]] = deque()
        self.slot_req: List[Optional[int]] = [None] * cfg.slots
        self.bank_version = 0
        self.steps = 0
        self.slot_steps = 0
        self._next_id = 0
        # per pending request: (submit time, steps at submit), then at
        # admit (submit time, queue_s, queued_steps, admit step)
        self._submitted: Dict[int, Tuple[float, int]] = {}
        self._admitted: Dict[int, Tuple[float, float, int, int]] = {}
        self._latency = _LatencyHistogram()
        self._served = 0
        self._abstained = 0
        self._queued = 0                 # served requests that waited a step
        self._queue_s = 0.0              # submit-to-admit seconds, summed

    # -- request lifecycle -------------------------------------------------
    def submit(self, req: ServeRequest) -> int:
        rid = self._next_id
        self._next_id += 1
        self._submitted[rid] = (time.perf_counter(), self.steps)
        self.queue.append((rid, req))
        return rid

    def pending(self) -> int:
        return len(self.queue) + sum(r is not None for r in self.slot_req)

    def _occupied_after_admit(self) -> int:
        """Slots the coming step runs occupied: those in flight plus the
        queued requests that fit into the free ones."""
        busy = sum(r is not None for r in self.slot_req)
        return busy + min(self.cfg.slots - busy, len(self.queue))

    def _pop_queued(self) -> Tuple[int, ServeRequest]:
        """The next queued request, stamped as admitted by this step."""
        rid, req = self.queue.popleft()
        t_sub, steps_at_submit = self._submitted.pop(rid)
        self._admitted[rid] = (t_sub, time.perf_counter() - t_sub,
                               self.steps - steps_at_submit, self.steps)
        return rid, req

    def _count_step(self, occupied: int) -> None:
        self.steps += 1
        self.slot_steps += occupied

    def step(self) -> List[ServeResponse]:
        raise NotImplementedError

    def drain(self) -> List[ServeResponse]:
        out: List[ServeResponse] = []
        while self.pending():
            out.extend(self.step())
        return out

    def run(self, requests) -> List[ServeResponse]:
        for r in requests:
            self.submit(r)
        return sorted(self.drain(), key=lambda r: r.request_id)

    # -- shared retire path ------------------------------------------------
    def _respond(self, rid: int, probs: np.ndarray, entropy: float,
                 **kw) -> ServeResponse:
        abstain = bool(abstain_mask(np.float32(entropy),
                                    self.cfg.entropy_threshold))
        t_sub, queue_s, queued_steps, admit_step = self._admitted.pop(rid)
        lat = time.perf_counter() - t_sub
        self._latency.add(lat)
        self._served += 1
        self._abstained += int(abstain)
        self._queued += int(queued_steps > 0)
        self._queue_s += queue_s
        return ServeResponse(request_id=rid, probs=probs,
                             entropy=float(entropy), abstain=abstain,
                             bank_version=self.bank_version,
                             latency_s=lat, queue_s=queue_s,
                             queued_steps=queued_steps,
                             admit_step=admit_step,
                             done_step=self.steps - 1, **kw)

    # -- accounting --------------------------------------------------------
    def compile_count(self) -> int:
        raise NotImplementedError

    def stats(self) -> Dict[str, float]:
        return {
            "served": float(self._served),
            "abstained": float(self._abstained),
            "abstain_rate": (self._abstained / self._served
                             if self._served else 0.0),
            "steps": float(self.steps),
            "slot_steps": float(self.slot_steps),
            "slot_occupancy": (self.slot_steps / (self.steps * self.cfg.slots)
                               if self.steps else 0.0),
            "queued_share": (self._queued / self._served
                             if self._served else 0.0),
            "queue_ms_mean": (1e3 * self._queue_s / self._served
                              if self._served else 0.0),
            "p50_ms": self._latency.percentile(50) * 1e3,
            "p99_ms": self._latency.percentile(99) * 1e3,
        }


class ClassifyEngine(ServingEngine):
    """Serving for single-step classifier requests.

    ``apply_fn(params, batch) -> logits`` is the same contract the eval
    engines use (for the zoo's classifiers: ``model.logits``). The slot
    table is a device-resident ``(slots, *input_shape)`` buffer; admits
    write rows in place via a traced-index update, the predict kernel is
    the shared :class:`BankPredictor` (``bma_predict_stacked`` + entropy)
    over that table. With ``slots == eval batch size`` the BMA
    probabilities are bitwise-equal to a :class:`ScanEvalEngine` pass.
    """

    def __init__(self, apply_fn: Callable, cfg: ServeConfig,
                 input_shape: Tuple[int, ...], stacked: Any = None,
                 node_axis: Optional[int] = None, mesh=None,
                 input_dtype=jnp.float32):
        super().__init__(cfg)
        self.predictor = BankPredictor(
            apply_fn, stacked=stacked, node_axis=node_axis, mesh=mesh,
            ensemble_axis=cfg.ensemble_axis)
        if stacked is not None:
            self.bank_version = 1
        self._xs = jnp.zeros((cfg.slots,) + tuple(input_shape), input_dtype)
        self._write = jax.jit(
            lambda xs, x, i: jax.lax.dynamic_update_index_in_dim(
                xs, x.astype(xs.dtype), i, 0))

    def install_bank(self, stacked) -> None:
        """Posterior hot swap — see :meth:`BankPredictor.install`."""
        self.predictor.install(stacked)
        self.bank_version += 1

    def num_samples(self) -> int:
        return self.predictor.num_samples()

    def step(self) -> List[ServeResponse]:
        occupied = self._occupied_after_admit()
        if not occupied:
            return []
        with TraceAnnotation("serve.step", step=self.steps,
                             occupied=occupied):
            with TraceAnnotation("serve.admit"):
                for i in range(self.cfg.slots):
                    if self.slot_req[i] is None and self.queue:
                        rid, req = self._pop_queued()
                        self._xs = self._write(self._xs, jnp.asarray(req.x), i)
                        self.slot_req[i] = rid
            with TraceAnnotation("serve.predict"):
                probs, ent = self.predictor.predict({"x": self._xs})
            with TraceAnnotation("serve.fetch"):
                probs = np.asarray(probs, np.float32)
                ent = np.asarray(ent, np.float32)
            self._count_step(occupied)
            done = []
            with TraceAnnotation("serve.retire"):
                for i in range(self.cfg.slots):
                    rid = self.slot_req[i]
                    if rid is None:
                        continue
                    done.append(self._respond(rid, probs[i], float(ent[i])))
                    self.slot_req[i] = None
        return done

    def compile_count(self) -> int:
        return self.predictor.compile_count() + self._write._cache_size()

    def stats(self) -> Dict[str, float]:
        """The shared stats, and how the predict programs ran the bank:
        traces that ran it as one packed forward, and member by member."""
        out = super().stats()
        out["bma_packed_compiles"] = float(self.predictor.packed_traces)
        out["bma_per_member_compiles"] = float(
            self.predictor.per_member_traces)
        return out


class DecodeEngine(ServingEngine):
    """Continuous batching for autoregressive decode under BMA.

    State lives in fixed-shape device tables:

    * ``caches`` — the model's B=1 decode cache with two extra leading
      axes ``(M, slots, ...)``: one KV lane per (posterior sample, slot).
      Built once; admits reset a lane from the pristine init (attention
      masks unwritten rows via ``slot_pos = -1``, so a reset lane decodes
      bitwise-identically to a fresh cache), retires just mark the slot
      free. Bank swaps never touch it.
    * ``tokens (slots, 1)`` / ``pos (slots,)`` — per-slot last token and
      decode position (positions are independent per lane because the
      inner vmap batches the model's scalar ``pos``).
    * ``keys (slots, 2)`` — per-request PRNG keys; each step samples with
      ``fold_in(key, pos)`` so a request's token stream depends only on
      its own seed and position, never on batch composition.

    One compiled step advances every lane: outer vmap over the M bank
    samples, inner vmap over slots, softmax-averaged (BMA) next-token
    distribution per slot, categorical sample per lane. Idle lanes
    decode garbage into their own cache at fixed cost and are reset on
    admit; the alternative — masking them out — would make the kernel
    shape-dependent on occupancy.
    """

    def __init__(self, model, cfg: ServeConfig, stacked: Any = None,
                 mesh=None):
        super().__init__(cfg)
        if model.decode_step is None:
            raise ValueError(f"{model.cfg.name} has no decode step")
        if cfg.max_new_tokens > cfg.max_len:
            raise ValueError("max_new_tokens exceeds the KV cache length")
        self.model = model
        self.mesh = mesh
        self._stacked = None
        self._num_samples = 0
        self._fresh1 = model.init_decode_state(1, cfg.max_len)
        self._caches = None
        self._tokens = jnp.zeros((cfg.slots, 1), jnp.int32)
        self._pos = jnp.zeros((cfg.slots,), jnp.int32)
        self._keys = jnp.zeros((cfg.slots, 2), jnp.uint32)
        self.slot_left: List[int] = [0] * cfg.slots
        self._slot_toks: Dict[int, List[int]] = {}
        self._slot_ents: Dict[int, List[float]] = {}
        self._step_fn = jax.jit(self._decode_all)
        self._admit_fn = jax.jit(self._admit)
        if stacked is not None:
            self.install_bank(stacked)

    # -- bank lifecycle ----------------------------------------------------
    def install_bank(self, stacked) -> None:
        """Atomic posterior hot swap between steps.

        The KV lane tables are sized by the sample count M, so a swap
        must keep M constant — which is also what keeps the compiled
        step valid (zero recompiles) and the caches untouched (zero
        reallocation: steady device memory across any number of swaps).
        """
        m = int(jax.tree.leaves(stacked)[0].shape[0])
        if self._stacked is not None and m != self._num_samples:
            raise ValueError(
                f"hot swap changed the sample count {self._num_samples} "
                f"-> {m}; the resident KV lanes are sized by it")
        if self.mesh is not None and self.cfg.ensemble_axis:
            stacked = place_ensemble(stacked, self.mesh,
                                     self.cfg.ensemble_axis)
        if self._caches is None:
            s = self.cfg.slots
            self._caches = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[None, None], (m, s) + x.shape).copy(),
                self._fresh1)
            if self.mesh is not None and self.cfg.ensemble_axis:
                # pin shardings once: KV lanes follow the bank's sample
                # axis, per-slot state is replicated. Leaving them
                # uncommitted lets GSPMD re-choose shardings call to
                # call, which shows up as spurious recompiles.
                from jax.sharding import NamedSharding, PartitionSpec as P
                # trailing-None-free spec: jit outputs come back with the
                # normalized form, and spec equality is part of the jit
                # cache key — P(ax) and P(ax, None, ...) compile twice
                lanes = NamedSharding(self.mesh, P(self.cfg.ensemble_axis))
                self._caches = jax.tree.map(
                    lambda x: jax.device_put(x, lanes), self._caches)
                rep = lambda x: jax.device_put(
                    x, NamedSharding(self.mesh, P()))
                self._tokens = rep(self._tokens)
                self._pos = rep(self._pos)
                self._keys = rep(self._keys)
            self._num_samples = m
        self._stacked = stacked          # the swap: one reference write
        self.bank_version += 1

    def num_samples(self) -> int:
        return self._num_samples

    # -- compiled kernels --------------------------------------------------
    def _pin_lanes(self, caches):
        """Constrain KV-lane shardings to the input layout (sample axis on
        the ensemble mesh axis) so every call compiles identically."""
        if self.mesh is None or not self.cfg.ensemble_axis:
            return caches
        from jax.sharding import NamedSharding, PartitionSpec as P
        lanes = NamedSharding(self.mesh, P(self.cfg.ensemble_axis))
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, lanes), caches)

    def _pin_rep(self, tree):
        if self.mesh is None or not self.cfg.ensemble_axis:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(self.mesh, P())
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, rep), tree)

    def _decode_all(self, stacked, caches, tokens, pos, keys):
        temp = self.cfg.temperature

        def per_slot(params, cache, tok, p):
            new_cache, logits = self.model.decode_step(
                params, cache, tok[None, :], p)
            return new_cache, logits[0, -1]

        def per_sample(params, cache):
            return jax.vmap(
                lambda c, t, p: per_slot(params, c, t, p))(cache, tokens, pos)

        new_caches, logits = jax.vmap(per_sample)(stacked, caches)
        probs = jnp.mean(
            jax.nn.softmax(logits.astype(jnp.float32) / temp, axis=-1),
            axis=0)                                     # (slots, V) BMA
        ent = predictive_entropy(probs)

        def sample(k, p, pr):
            kk = jax.random.fold_in(k, p)
            return jax.random.categorical(
                kk, jnp.log(jnp.maximum(pr, 1e-12)))

        nxt = jax.vmap(sample)(keys, pos, probs).astype(jnp.int32)
        return (self._pin_lanes(new_caches),
                *self._pin_rep((nxt[:, None], pos + 1, probs, ent)))

    def _admit(self, caches, tokens, pos, keys, i, tok0, seed):
        # reset lane i (all M sample copies) to the pristine init; the
        # attention mask (slot_pos = -1) makes the lane decode as fresh
        caches = jax.tree.map(lambda c, f: c.at[:, i].set(f),
                              caches, self._fresh1)
        tokens = tokens.at[i, 0].set(tok0)
        pos = pos.at[i].set(0)
        keys = keys.at[i].set(jax.random.PRNGKey(seed))
        return (self._pin_lanes(caches),
                *self._pin_rep((tokens, pos, keys)))

    # -- the serving loop --------------------------------------------------
    def step(self) -> List[ServeResponse]:
        if self._stacked is None:
            raise ValueError("no bank installed; call install_bank(stacked)")
        occupied = self._occupied_after_admit()
        if not occupied:
            return []
        with TraceAnnotation("serve.step", step=self.steps,
                             occupied=occupied):
            with TraceAnnotation("serve.admit"):
                for i in range(self.cfg.slots):
                    if self.slot_req[i] is None and self.queue:
                        rid, req = self._pop_queued()
                        (self._caches, self._tokens, self._pos,
                         self._keys) = self._admit_fn(
                            self._caches, self._tokens, self._pos,
                            self._keys, i, req.prompt_token, req.seed)
                        self.slot_req[i] = rid
                        self.slot_left[i] = req.max_new_tokens or \
                            self.cfg.max_new_tokens
                        self._slot_toks[rid] = []
                        self._slot_ents[rid] = []
            with TraceAnnotation("serve.predict"):
                (self._caches, self._tokens, self._pos, probs,
                 ent) = self._step_fn(self._stacked, self._caches,
                                      self._tokens, self._pos, self._keys)
            with TraceAnnotation("serve.fetch"):
                toks = np.asarray(self._tokens[:, 0])
                ents = np.asarray(ent, np.float32)
            probs_h = None                   # fetched lazily on retire
            self._count_step(occupied)
            done = []
            with TraceAnnotation("serve.retire"):
                for i in range(self.cfg.slots):
                    rid = self.slot_req[i]
                    if rid is None:
                        continue
                    self._slot_toks[rid].append(int(toks[i]))
                    self._slot_ents[rid].append(float(ents[i]))
                    self.slot_left[i] -= 1
                    if self.slot_left[i] == 0:
                        if probs_h is None:
                            probs_h = np.asarray(probs, np.float32)
                        t = np.asarray(self._slot_toks.pop(rid), np.int32)
                        e = np.asarray(self._slot_ents.pop(rid), np.float32)
                        done.append(self._respond(
                            rid, probs_h[i], float(e.mean()),
                            tokens=t, token_entropy=e))
                        self.slot_req[i] = None
        return done

    def compile_count(self) -> int:
        return self._step_fn._cache_size() + self._admit_fn._cache_size()
