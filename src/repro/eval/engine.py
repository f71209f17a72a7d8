"""Device-resident evaluation engines (DESIGN.md §10).

The training side went device-resident in PR 2/4 (scan-fused rounds,
shard_map SPMD), but evaluation stayed a host loop: one jit dispatch per
posterior sample (``bma_predict``'s traced Python loop), full-dataset
probability materialization, and host-side numpy metric reductions. On
the paper's protocol — BMA over S posterior samples × K node chains ×
every scenario cell of the shift matrix — that host loop is the slowest
remaining path in the repo.

This module evaluates entirely on device:

* :class:`ScanEvalEngine` — one donated ``lax.scan`` over fixed-size
  evaluation batches; inside the body a single ``vmap`` over the stacked
  posterior samples (``DeviceSampleBank.stacked``) produces the BMA
  predictive distribution, and fused streaming accumulators update
  accuracy, NLL, Brier, predictive entropy and the ECE reliability bins
  of ``core/calibration.py`` in one pass. The host sees one dispatch and
  one tiny accumulator transfer per dataset.
* :class:`HostEvalEngine` — the per-batch dispatch loop kept as the
  reference oracle: same per-batch statistics kernel, Python loop,
  host-ordered accumulation. The equivalence tests pin the scan engine
  to it bitwise (single device).
* :class:`ShardEvalEngine` — the SPMD path matching PR 4's
  ``ShardRoundEngine``: the stacked bank stays node-sharded over the fed
  mesh axis, each program instance computes its local nodes' probability
  sums, one ``psum`` per batch completes the BMA mean, every shard then
  scores a disjoint slice of the batch and the metric accumulators are
  psum-reduced across the fed axis at the end — evaluation scales with
  the same mesh the shard engine trains on.

Metrics are defined through sufficient statistics (:class:`EvalAccum`)
shared by all three engines, so "what a metric means" lives in exactly
one place (:func:`update_accum` / :func:`finalize`).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.calibration import ReliabilityBins
from repro.core.posterior import bma_predict_stacked, predictive_entropy


def abstain_mask(entropy: jnp.ndarray, threshold: float) -> jnp.ndarray:
    """Entropy-gated selective prediction: True = abstain/route-to-human.

    The one abstain rule (DESIGN.md §14), shared between the serving
    engine's per-request gate and the eval accumulators' selective
    accounting — a threshold tuned on an :class:`EvalReport` transfers to
    serving unchanged.
    """
    return entropy > threshold


class EvalAccum(NamedTuple):
    """Streaming sufficient statistics for one evaluation pass.

    A pure streaming reduction — order-fixed, so accumulation is deterministic.
    """
    n: jax.Array             # () f32 — examples scored (mask-weighted)
    correct: jax.Array       # () f32 — argmax hits
    nll_sum: jax.Array       # () f32 — summed -log p(y)
    brier_sum: jax.Array     # () f32 — summed squared-error to onehot
    ent_sum: jax.Array       # () f32 — summed predictive entropy
    bin_counts: jax.Array    # (O,) f32 — reliability-bin occupancy
    bin_conf: jax.Array      # (O,) f32 — summed confidence per bin
    bin_acc: jax.Array       # (O,) f32 — summed accuracy per bin
    # entropy-gated selective prediction (0-valued at threshold = inf)
    abstained: jax.Array     # () f32 — examples over the entropy threshold
    kept_correct: jax.Array  # () f32 — argmax hits among answered examples


class EvalReport(NamedTuple):
    """Finalized metrics (host floats) + the reliability bins.

    Finalization is deterministic in the accumulated statistics.
    """
    accuracy: float
    ece: float
    mce: float
    nll: float
    brier: float
    entropy: float
    # mean signed confidence-accuracy gap over occupied bins; positive =
    # overconfident (the Fig. 4 safety signal)
    overconf_gap: float
    count: float
    bins: ReliabilityBins
    # selective prediction under the entropy gate (abstain_mask): the
    # fraction routed to a human, and accuracy over the answered rest
    # (degenerates to 0 / accuracy at the default threshold = inf)
    abstain_rate: float = 0.0
    kept_accuracy: float = float("nan")


def init_accum(num_bins: int) -> EvalAccum:
    z = jnp.zeros((), jnp.float32)
    zb = jnp.zeros((num_bins,), jnp.float32)
    return EvalAccum(z, z, z, z, z, zb, zb, zb, z, z)


def update_accum(accum: EvalAccum, probs: jnp.ndarray, labels: jnp.ndarray,
                 mask: jnp.ndarray, num_bins: int,
                 entropy_threshold: float = float("inf")) -> EvalAccum:
    """Fold one (B, C) probability batch into the accumulators.

    ``mask`` (B,) zeroes padded tail examples. The bin rule matches
    ``core.calibration.reliability_bins`` (right-inclusive, Guo et al.
    '17), so finalized ECE/MCE agree with the host formulas up to batch
    summation order. ``entropy_threshold`` feeds the selective-prediction
    accumulators only; every other statistic still scores all examples.
    """
    probs = probs.astype(jnp.float32)
    mask = mask.astype(jnp.float32)
    if labels.ndim > 1:
        # token-level prediction (B, T, C): every label position is one
        # scored example, the batch mask broadcasts over the extra dims
        mask = jnp.broadcast_to(
            mask.reshape(mask.shape + (1,) * (labels.ndim - mask.ndim)),
            labels.shape)
        probs = probs.reshape(-1, probs.shape[-1])
        labels = labels.reshape(-1)
        mask = mask.reshape(-1)
    conf = jnp.max(probs, axis=-1)
    pred = jnp.argmax(probs, axis=-1)
    correct = (pred == labels).astype(jnp.float32) * mask
    p_label = jnp.take_along_axis(probs, labels[:, None], axis=-1)[:, 0]
    nll = -jnp.log(jnp.maximum(p_label, 1e-12)) * mask
    onehot = jax.nn.one_hot(labels, probs.shape[-1], dtype=jnp.float32)
    brier = jnp.sum(jnp.square(probs - onehot), axis=-1) * mask
    ent_raw = predictive_entropy(probs)
    ent = ent_raw * mask
    abstain = abstain_mask(ent_raw, entropy_threshold).astype(jnp.float32)
    idx = jnp.clip(jnp.ceil(conf * num_bins).astype(jnp.int32) - 1,
                   0, num_bins - 1)
    return EvalAccum(
        n=accum.n + jnp.sum(mask),
        correct=accum.correct + jnp.sum(correct),
        nll_sum=accum.nll_sum + jnp.sum(nll),
        brier_sum=accum.brier_sum + jnp.sum(brier),
        ent_sum=accum.ent_sum + jnp.sum(ent),
        bin_counts=accum.bin_counts.at[idx].add(mask),
        bin_conf=accum.bin_conf.at[idx].add(conf * mask),
        bin_acc=accum.bin_acc.at[idx].add(correct),
        abstained=accum.abstained + jnp.sum(abstain * mask),
        kept_correct=accum.kept_correct + jnp.sum(correct * (1.0 - abstain)),
    )


def finalize(accum: EvalAccum) -> EvalReport:
    """Sufficient statistics -> metrics (host floats)."""
    accum = jax.tree.map(np.asarray, accum)
    num_bins = accum.bin_counts.shape[0]
    n = max(float(accum.n), 1.0)
    safe = np.maximum(accum.bin_counts, 1.0)
    conf_b = accum.bin_conf / safe
    acc_b = accum.bin_acc / safe
    w = accum.bin_counts / n
    gaps = acc_b - conf_b
    occ = accum.bin_counts > 0
    bins = ReliabilityBins(
        bin_confidence=conf_b.astype(np.float32),
        bin_accuracy=acc_b.astype(np.float32),
        bin_counts=accum.bin_counts.astype(np.float32),
        edges=np.linspace(0.0, 1.0, num_bins + 1, dtype=np.float32),
    )
    return EvalReport(
        accuracy=float(accum.correct / n),
        ece=float(np.sum(w * np.abs(gaps))),
        mce=float(np.max(np.where(occ, np.abs(gaps), 0.0))),
        nll=float(accum.nll_sum / n),
        brier=float(accum.brier_sum / n),
        entropy=float(accum.ent_sum / n),
        overconf_gap=float(np.sum(np.where(occ, conf_b - acc_b, 0.0))
                           / max(int(occ.sum()), 1)),
        count=float(accum.n),
        bins=bins,
        abstain_rate=float(accum.abstained / n),
        kept_accuracy=float(accum.kept_correct
                            / max(float(accum.n - accum.abstained), 1.0)),
    )


# --------------------------------------------------------------------------
# Batching
# --------------------------------------------------------------------------

def stack_eval_batches(data: Dict[str, np.ndarray], batch_size: int
                       ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """Pad + reshape a dataset to (nb, B, ...) stacks with a (nb, B) mask.

    The padded tail repeats example 0 (shapes stay valid for any model)
    and is masked out of every statistic.
    """
    n = len(data["y"])
    if n == 0:
        raise ValueError("empty evaluation dataset")
    b = batch_size
    nb = -(-n // b)
    pad = nb * b - n
    out = {}
    for f, v in data.items():
        v = np.asarray(v)
        if pad:
            v = np.concatenate([v, np.repeat(v[:1], pad, axis=0)])
        out[f] = jnp.asarray(v.reshape((nb, b) + v.shape[1:]))
    mask = np.ones(nb * b, np.float32)
    if pad:
        mask[n:] = 0.0
    return out, jnp.asarray(mask.reshape(nb, b))


def as_stacked(params: Any) -> Any:
    """Wrap point params into a length-1 stacked sample axis (S=1)."""
    return jax.tree.map(lambda x: jnp.asarray(x)[None], params)


def lm_apply_fn(model) -> Callable:
    """Next-token prediction fn for token batches: trim any non-text
    prefix (VLM image patches), drop the last position. Labels are
    ``tokens[:, 1:]`` — the one LM evaluation contract, shared by
    ``FedTrainer`` and ``launch/train.py`` so their metrics agree."""
    def apply(p, b):
        lg = model.logits(p, b)
        t = b["tokens"].shape[1]
        return lg[:, lg.shape[1] - t:][:, :-1]
    return apply


# --------------------------------------------------------------------------
# Engines
# --------------------------------------------------------------------------

class ScanEvalEngine:
    """Fused single-dispatch evaluation: scan over batches, vmap over the
    posterior bank, streaming metric accumulators.

    ``apply_fn(params, batch) -> logits``; ``stacked`` carries a leading
    sample axis ``(S, ...)`` (``DeviceSampleBank.stacked``) and, with
    ``node_axis=1``, a node-chain axis ``(S, K, ...)`` — the same BMA
    semantics as :func:`repro.core.posterior.bma_predict_stacked`.

    Bitwise-equivalent to :class:`HostEvalEngine` (tier-1 gated).
    """

    name = "scan"

    def __init__(self, apply_fn: Callable, num_bins: int = 10,
                 batch_size: int = 64,
                 entropy_threshold: float = float("inf")):
        self.apply_fn = apply_fn
        self.num_bins = int(num_bins)
        self.batch_size = int(batch_size)
        self.entropy_threshold = float(entropy_threshold)
        self._fns = {}

    def _fn(self, node_axis: Optional[int], with_probs: bool,
            weighted: bool = False):
        key = (node_axis, with_probs, weighted)
        if key not in self._fns:
            def run(stacked, weights, batches, masks, accum0):
                def body(acc, xs):
                    batch, mask = xs
                    probs = bma_predict_stacked(self.apply_fn, stacked,
                                                batch, node_axis=node_axis,
                                                weights=weights)
                    acc = update_accum(acc, probs, batch["y"], mask,
                                      self.num_bins,
                                      self.entropy_threshold)
                    return acc, (probs if with_probs else None)
                return jax.lax.scan(body, accum0, (batches, masks))
            # the scan carry (the accumulators) updates in place inside the
            # loop; jit-level donation is pointless at these sizes (and the
            # deduped zero-scalar init buffers would alias)
            self._fns[key] = jax.jit(run)
        return self._fns[key]

    def evaluate(self, stacked, data: Dict[str, np.ndarray],
                 node_axis: Optional[int] = None,
                 return_probs: bool = False, weights=None):
        """One fused pass -> :class:`EvalReport` (and optionally the
        unpadded (N, C) BMA probabilities for diagram rendering).

        ``weights`` (optional ``(S,)``) switches the BMA mean to the
        age-discounted mixture; ``weights=None`` traces the pre-continual
        graph unchanged (bitwise-pinned against :class:`HostEvalEngine`)."""
        n = len(data["y"])
        if weights is not None:
            weights = jnp.asarray(weights, jnp.float32)
        batches, masks = stack_eval_batches(data, self.batch_size)
        accum, probs = self._fn(node_axis, return_probs,
                                weights is not None)(
            stacked, weights, batches, masks, init_accum(self.num_bins))
        report = finalize(accum)
        if return_probs:
            # (nb, B, ...) -> (nb*B, ...): flatten only the batch stacking,
            # keeping token-level (T, C) tails intact (the LM path)
            probs = np.asarray(probs, np.float32)
            return report, probs.reshape((-1,) + probs.shape[2:])[:n]
        return report


class HostEvalEngine:
    """Per-batch dispatch loop — the reference oracle.

    Runs the *same* per-batch statistics kernel as the scan body, one jit
    call per batch, accumulating on device in host loop order; kept
    deliberately boring so the fused engine has a trustworthy target.

    Deterministic in (stacked, data, weights) — the bitwise reference.
    """

    name = "host"

    def __init__(self, apply_fn: Callable, num_bins: int = 10,
                 batch_size: int = 64,
                 entropy_threshold: float = float("inf")):
        self.apply_fn = apply_fn
        self.num_bins = int(num_bins)
        self.batch_size = int(batch_size)
        self.entropy_threshold = float(entropy_threshold)
        self._fns = {}

    def _step(self, node_axis: Optional[int], weighted: bool = False):
        key = (node_axis, weighted)
        if key not in self._fns:
            def step(stacked, weights, batch, mask, acc):
                probs = bma_predict_stacked(self.apply_fn, stacked, batch,
                                            node_axis=node_axis,
                                            weights=weights)
                return update_accum(acc, probs, batch["y"], mask,
                                    self.num_bins,
                                    self.entropy_threshold), probs
            self._fns[key] = jax.jit(step)
        return self._fns[key]

    def evaluate(self, stacked, data: Dict[str, np.ndarray],
                 node_axis: Optional[int] = None,
                 return_probs: bool = False, weights=None):
        n = len(data["y"])
        if weights is not None:
            weights = jnp.asarray(weights, jnp.float32)
        batches, masks = stack_eval_batches(data, self.batch_size)
        nb = masks.shape[0]
        acc = init_accum(self.num_bins)
        step = self._step(node_axis, weights is not None)
        all_probs = []
        for i in range(nb):
            batch = {f: v[i] for f, v in batches.items()}
            acc, probs = step(stacked, weights, batch, masks[i], acc)
            if return_probs:
                all_probs.append(np.asarray(probs, np.float32))
        report = finalize(acc)
        if return_probs:
            return report, np.concatenate(all_probs)[:n]
        return report


class ShardEvalEngine:
    """SPMD evaluation over a node-sharded posterior bank (DESIGN.md §10).

    ``stacked`` leaves are ``(S, K, ...)`` with the node axis K sharded
    over ``mesh``'s ``fed_axis`` (the layout :class:`ShardRoundEngine`
    trains in). Per batch, each program instance sums softmax
    probabilities over its local node chains, one ``lax.psum`` completes
    the global BMA mean, and each shard then scores a disjoint
    ``B/num_shards`` slice of the batch; the metric accumulators are
    psum-reduced across the fed axis after the scan, so the returned
    statistics are replicated and identical on every shard.

    Matches the host oracle to float tolerance (conv reductions reorder under shard_map); node-dropping and age weights are exact.
    """

    name = "shard"

    def __init__(self, apply_fn: Callable, mesh, fed_axis: str = "fed",
                 num_bins: int = 10, batch_size: int = 64,
                 entropy_threshold: float = float("inf")):
        self.apply_fn = apply_fn
        self.mesh = mesh
        self.fed_axis = fed_axis
        self.num_shards = int(mesh.shape[fed_axis])
        self.num_bins = int(num_bins)
        self.entropy_threshold = float(entropy_threshold)
        # per-shard batch slices must tile the batch exactly
        self.batch_size = -(-int(batch_size) // self.num_shards
                            ) * self.num_shards
        self._fns = {}

    def place(self, stacked):
        """device_put the stacked bank with the node axis (dim 1) sharded."""
        s = NamedSharding(self.mesh, P(None, self.fed_axis))
        return jax.device_put(stacked, s)

    def _fn(self, stacked, k_total: int, weighted: bool = False):
        key = (k_total, weighted)
        if key not in self._fns:
            axis, num_bins = self.fed_axis, self.num_bins
            ent_thr = self.entropy_threshold
            slice_b = self.batch_size // self.num_shards

            def make_local(with_weights: bool):
                def run(stacked_l, weights, batches, masks):
                    r = jax.lax.axis_index(axis)
                    own = (jnp.arange(self.batch_size) // slice_b) == r

                    def body(acc, xs):
                        batch, mask = xs
                        # local partial BMA: sum of softmax over (S, local K)
                        logits = jax.vmap(lambda p: jax.vmap(
                            lambda q: self.apply_fn(q, batch))(p))(stacked_l)
                        p = jax.nn.softmax(logits.astype(jnp.float32),
                                           axis=-1)
                        if not with_weights:
                            p_sum = jnp.sum(p, axis=(0, 1))
                            probs = jax.lax.psum(p_sum, axis) / (
                                logits.shape[0] * k_total)
                        else:
                            # age-weighted: psum the per-sample node sums,
                            # node-mean, then mix samples with the weights
                            p_s = jax.lax.psum(jnp.sum(p, axis=1),
                                               axis) / k_total
                            w = weights / jnp.maximum(
                                jnp.sum(weights), jnp.float32(1e-12))
                            probs = jnp.einsum("s,s...->...", w, p_s)
                        acc = update_accum(acc, probs, batch["y"],
                                           mask * own, num_bins, ent_thr)
                        return acc, None

                    acc, _ = jax.lax.scan(body, init_accum(num_bins),
                                          (batches, masks))
                    # psum the metric accumulators across the fed mesh axis
                    return jax.tree.map(lambda x: jax.lax.psum(x, axis),
                                        acc)

                if with_weights:
                    return run
                return lambda stacked_l, batches, masks: run(
                    stacked_l, None, batches, masks)

            stacked_specs = jax.tree.map(lambda _: P(None, self.fed_axis),
                                         stacked)
            accum_specs = jax.tree.map(lambda _: P(),
                                       init_accum(self.num_bins))
            in_specs = ((stacked_specs, P(), P(), P()) if weighted
                        else (stacked_specs, P(), P()))
            fn = jax.shard_map(make_local(weighted), mesh=self.mesh,
                               in_specs=in_specs, out_specs=accum_specs,
                               check_vma=False)
            self._fns[key] = jax.jit(fn)
        return self._fns[key]

    def evaluate(self, stacked, data: Dict[str, np.ndarray],
                 weights=None) -> EvalReport:
        k_total = jax.tree.leaves(stacked)[0].shape[1]
        stacked = self.place(stacked)
        if weights is not None:
            weights = jnp.asarray(weights, jnp.float32)
        batches, masks = stack_eval_batches(data, self.batch_size)
        fn = self._fn(stacked, k_total, weights is not None)
        if weights is not None:
            accum = fn(stacked, weights, batches, masks)
        else:
            accum = fn(stacked, batches, masks)
        return finalize(accum)


def make_eval_engine(name: str, apply_fn: Callable, num_bins: int = 10,
                     batch_size: int = 64, mesh=None, fed_axis: str = "fed",
                     entropy_threshold: float = float("inf")):
    """Factory mirroring ``train.engine.make_engine``."""
    if name == "scan":
        return ScanEvalEngine(apply_fn, num_bins, batch_size,
                              entropy_threshold)
    if name == "host":
        return HostEvalEngine(apply_fn, num_bins, batch_size,
                              entropy_threshold)
    if name == "shard":
        if mesh is None:
            from repro.launch.mesh import make_fed_mesh
            mesh = make_fed_mesh(fed_axis=fed_axis)
        return ShardEvalEngine(apply_fn, mesh, fed_axis, num_bins,
                               batch_size,
                               entropy_threshold=entropy_threshold)
    raise ValueError(f"unknown eval engine {name!r}; "
                     f"use 'scan', 'host' or 'shard'")
