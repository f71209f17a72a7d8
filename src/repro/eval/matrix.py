"""Scenario × algorithm × pipeline calibration matrix (DESIGN.md §10).

Evaluates trained models (fresh training runs or checkpointed params)
across the shift-family registry (``repro.data.scenarios``) through the
fused eval engine, producing one calibration row per
(scenario, severity, algorithm, pipeline) cell: accuracy, ECE, NLL,
Brier, predictive entropy and the signed overconfidence gap.

Reduced-scale training defaults follow DESIGN.md §7 (same values as
``benchmarks/common.py``); the paper-scale knobs are in the comments
there. The CI claims gate (``benchmarks/check_regression.py --claims``)
runs :func:`run_claims_smoke` — a tiny fixed-seed slice of this matrix —
and hard-fails when the paper's ordering claims break.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.config import FedConfig, get_arch
from repro.data.partition import partition_iid
from repro.data.radar import make_dataset
from repro.data.scenarios import make_scenario_dataset
from repro.eval.engine import EvalReport, ScanEvalEngine, as_stacked
from repro.models import get_model


@dataclass(frozen=True)
class MatrixCell:
    """One (scenario, severity, algorithm) cell; pure in the spec and seed — bitwise reproducible, which is what the claims gate relies on."""
    scenario: str
    severity: float
    algorithm: str
    pipeline: str          # codec DSL ("" = the spec's compressor)
    report: EvalReport
    train_wall_s: float = 0.0
    eval_wall_s: float = 0.0

    def row(self) -> Dict[str, float]:
        r = self.report
        return {
            "scenario": self.scenario, "severity": self.severity,
            "algorithm": self.algorithm, "pipeline": self.pipeline or "-",
            "accuracy": r.accuracy, "ece": r.ece, "nll": r.nll,
            "brier": r.brier, "overconf_gap": r.overconf_gap,
            "count": r.count,
        }


@dataclass(frozen=True)
class MatrixSpec:
    """One matrix run: what to train, what to evaluate it on.

    Pure data: a matrix run is a deterministic function of (spec, seed).
    """
    algorithms: Sequence[str] = ("cdbfl", "cffl")
    pipelines: Sequence[str] = ("",)
    # (scenario, severity) cells; every trained model sees every cell
    cells: Sequence[Tuple[str, float]] = (("clean", 0.0),
                                          ("day23_critical", 0.5))
    # reduced-scale training world (DESIGN.md §7; paper: K=10, T=800)
    nodes: int = 5
    per_node: int = 24
    rounds: int = 150
    burn_in_frac: float = 2.0 / 3.0
    local_steps: int = 8
    minibatch: int = 10
    eta: float = 3e-3
    zeta: float = 0.3
    temperature: float = 0.2
    # the paper's operator: plain top-k at 1% (run_method's default in
    # benchmarks/common.py — fig4 rows stay comparable across PRs)
    compressor: str = "topk"
    compress_ratio: float = 0.01
    topology: str = "full"
    eval_examples: int = 200
    eval_batch_size: int = 64
    seed: int = 0
    arch: str = "lenet-radar"


def _chaos_blocks(spec: MatrixSpec):
    """``REPRO_CHAOS=1``: train every matrix cell under protocol-level
    chaos — 20% frame erasure recovered by selective-repeat ARQ, 20%
    stragglers, and one mid-run node death/rejoin (DESIGN.md §12). The
    CI chaos job sets this to prove the calibration claims survive the
    reliability layer, not just the clean channel."""
    if os.environ.get("REPRO_CHAOS", "") in ("", "0"):
        return None, None
    from repro.config import ParticipationConfig, TransportConfig
    transport = TransportConfig(mtu=64, erasure=0.2, arq=True, max_retries=2)
    participation = ParticipationConfig(
        straggler_prob=0.2,
        dead=((spec.nodes - 1, spec.rounds // 3, 2 * spec.rounds // 3),))
    return transport, participation


def _train_one(spec: MatrixSpec, algorithm: str, pipeline: str):
    from repro.train import FedTrainer   # deferred: trainer imports eval
    cfg = get_arch(spec.arch).reduced
    model = get_model(cfg)
    train = make_dataset(spec.nodes * spec.per_node, hw=cfg.input_hw,
                         day=1, seed=spec.seed)
    shards = partition_iid(train, spec.nodes, seed=spec.seed)
    transport, participation = _chaos_blocks(spec)
    fed = FedConfig(
        num_nodes=spec.nodes, local_steps=spec.local_steps, eta=spec.eta,
        zeta=spec.zeta, rounds=spec.rounds,
        burn_in=int(spec.rounds * spec.burn_in_frac),
        compressor=pipeline or spec.compressor,
        compress_ratio=spec.compress_ratio, topology=spec.topology,
        temperature=spec.temperature, algorithm=algorithm, seed=spec.seed,
        transport=transport, participation=participation,
    )
    tr = FedTrainer(model, fed, shards, minibatch=spec.minibatch,
                    seed=spec.seed, eval_batch_size=spec.eval_batch_size)
    t0 = time.time()
    tr.run(rounds=spec.rounds)
    return cfg, tr, time.time() - t0


def _cell_dataset(spec: MatrixSpec, cfg, scenario: str, severity: float
                  ) -> Dict[str, np.ndarray]:
    return make_scenario_dataset(scenario, severity, spec.eval_examples,
                                 hw=cfg.input_hw, seed=spec.seed + 90)


def run_matrix(spec: MatrixSpec, log=print,
               trainers: Optional[Dict] = None) -> List[MatrixCell]:
    """Train every (algorithm, pipeline), evaluate every scenario cell.

    Pass a dict as ``trainers`` to receive the trained ``FedTrainer``
    per (algorithm, pipeline) — the claims gate re-scores cells on them.
    """
    cells: List[MatrixCell] = []
    for algorithm in spec.algorithms:
        for pipeline in spec.pipelines:
            cfg, tr, train_s = _train_one(spec, algorithm, pipeline)
            if trainers is not None:
                trainers[(algorithm, pipeline)] = tr
            for scenario, severity in spec.cells:
                ds = _cell_dataset(spec, cfg, scenario, severity)
                t0 = time.time()
                rep = tr.eval_report(ds)
                cells.append(MatrixCell(
                    scenario=scenario, severity=float(severity),
                    algorithm=algorithm, pipeline=pipeline, report=rep,
                    train_wall_s=train_s, eval_wall_s=time.time() - t0))
                if log:
                    log(f"  [{algorithm}|{pipeline or '-'}] "
                        f"{scenario}@{severity:g}: acc={rep.accuracy:.4f} "
                        f"ece={rep.ece:.4f} nll={rep.nll:.4f} "
                        f"gap={rep.overconf_gap:+.4f}")
    return cells


def evaluate_params_matrix(params, arch: str,
                           cells: Sequence[Tuple[str, float]],
                           eval_examples: int = 200, seed: int = 0,
                           batch_size: int = 64, node_axis: Optional[int] = 0,
                           log=print) -> List[MatrixCell]:
    """Point-estimate matrix for checkpointed params (no training run).

    ``node_axis=0`` treats a leading params axis as node chains (the
    FedState layout); ``None`` scores a single replica.
    """
    cfg = get_arch(arch).reduced if _looks_reduced(params, arch) else \
        get_arch(arch).config
    model = get_model(cfg)
    stacked = as_stacked(params)
    engine = ScanEvalEngine(lambda p, b: model.logits(p, b),
                            batch_size=batch_size)
    out: List[MatrixCell] = []
    for scenario, severity in cells:
        ds = make_scenario_dataset(scenario, severity, eval_examples,
                                   hw=cfg.input_hw, seed=seed + 90)
        t0 = time.time()
        rep = engine.evaluate(stacked, ds,
                              node_axis=(node_axis + 1
                                         if node_axis is not None else None))
        out.append(MatrixCell(scenario=scenario, severity=float(severity),
                              algorithm="checkpoint", pipeline="",
                              report=rep, eval_wall_s=time.time() - t0))
        if log:
            log(f"  [checkpoint] {scenario}@{severity:g}: "
                f"acc={rep.accuracy:.4f} ece={rep.ece:.4f}")
    return out


def _looks_reduced(params, arch: str) -> bool:
    """Heuristic: match checkpoint params against the reduced config's
    input resolution (fc1 input width differs between the two)."""
    import jax
    try:
        reduced = get_arch(arch).reduced
        model = get_model(reduced)
        like = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        flat_p = {tuple(np.shape(x)) for x in jax.tree.leaves(params)
                  if np.ndim(x) >= 2}
        flat_r = {tuple(x.shape) for x in jax.tree.leaves(like)
                  if len(x.shape) >= 2}
        # node-stacked checkpoints carry one leading axis
        stripped = {s[1:] for s in flat_p}
        return bool(flat_r & (flat_p | stripped))
    except Exception:
        return True


def matrix_markdown(cells: Sequence[MatrixCell]) -> str:
    lines = [
        "| scenario | severity | algorithm | pipeline | acc | ece | nll "
        "| brier | overconf_gap | n |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for c in cells:
        r = c.report
        lines.append(
            f"| {c.scenario} | {c.severity:g} | {c.algorithm} "
            f"| {c.pipeline or '-'} | {r.accuracy:.4f} | {r.ece:.4f} "
            f"| {r.nll:.4f} | {r.brier:.4f} | {r.overconf_gap:+.4f} "
            f"| {int(r.count)} |")
    return "\n".join(lines)


# --------------------------------------------------------------------------
# CI claims gate (benchmarks/check_regression.py --claims)
# --------------------------------------------------------------------------

#: the tiny fixed-seed slice the claims job runs — small enough for a PR
#: job, big enough that the gated claims hold with wide margins
CLAIMS_SPEC = MatrixSpec(
    algorithms=("cdbfl", "cffl"),
    pipelines=("",),
    cells=(("clean", 0.0), ("day23_critical", 1.0)),
    rounds=60, per_node=24, eval_examples=200, seed=0,
)

#: slack on the ECE ordering claim, mirroring bench_fig4's claim row.
#: NOTE (DESIGN.md §10): at DESIGN §7 reduced scale the cold-posterior
#: BMA is *under*confident, so the paper's raw "cdbfl ECE ≤ cffl ECE
#: under shift" ordering does not transfer — it is reported as a
#: warning. The hard gates below pin the claims that do transfer: the
#: shift genuinely degrades accuracy, the Bayesian model retains far
#: more predictive uncertainty under shift, and the frequentist model —
#: not the Bayesian one — is the one that turns overconfident.
CLAIMS_ECE_MARGIN = 0.02
CLAIMS_ACC_DROP_MIN = 0.15       # observed ≈ 0.53 at the claims seed
CLAIMS_ENTROPY_MARGIN = 0.15     # observed ≈ 0.53
CLAIMS_CFFL_GAP_RISE_MIN = 0.10  # observed ≈ 0.24


def run_claims_smoke(spec: MatrixSpec = CLAIMS_SPEC, log=print
                     ) -> Dict[str, object]:
    """Run the claims slice; hard-fail when the paper's transferable
    claims break, warn on the reduced-scale-fragile ECE ordering.

    Also re-scores the cdbfl shifted cell from scratch (fresh scenario
    synthesis + freshly-jitted engine) to prove the shifted calibration
    numbers are reproducible, not run-to-run noise.
    """
    trainers: Dict = {}
    cells = run_matrix(spec, log=log, trainers=trainers)
    by = {(c.algorithm, c.scenario): c for c in cells}
    shift_name, shift_sev = next((s, v) for s, v in spec.cells
                                 if s != "clean")

    failures: List[str] = []
    warnings: List[str] = []
    for c in cells:
        if not np.isfinite(c.report.ece):
            failures.append(f"{c.algorithm}/{c.scenario}: ECE is not finite "
                            f"({c.report.ece})")
    cd = by[("cdbfl", shift_name)].report
    cf = by[("cffl", shift_name)].report
    cd0 = by[("cdbfl", "clean")].report
    cf0 = by[("cffl", "clean")].report

    # reproducibility: a fresh dataset synthesis (pure in seed/severity)
    # scored through a freshly-jitted engine must reproduce the shifted
    # ECE bitwise — the whole cell is a function of the spec, nothing else
    cfg = get_arch(spec.arch).reduced
    ds_a = _cell_dataset(spec, cfg, shift_name, shift_sev)
    ds_b = _cell_dataset(spec, cfg, shift_name, shift_sev)
    if not (np.array_equal(ds_a["x"], ds_b["x"])
            and np.array_equal(ds_a["y"], ds_b["y"])):
        failures.append(f"scenario {shift_name}@{shift_sev} is not "
                        f"reproducible: two syntheses differ")
    tr = trainers[("cdbfl", spec.pipelines[0])]
    model = tr.model
    fresh = ScanEvalEngine(lambda p, b: model.logits(p, b),
                           batch_size=spec.eval_batch_size)
    rep2 = fresh.evaluate(tr._stacked_bank(), ds_b, node_axis=1)
    if rep2.ece != cd.ece:
        failures.append(
            f"shifted ECE not reproducible: fresh-engine re-score "
            f"{rep2.ece!r} != first score {cd.ece!r}")

    # the shift must genuinely bite (precondition of the whole argument)
    for name, clean, shifted in (("cdbfl", cd0, cd), ("cffl", cf0, cf)):
        drop = clean.accuracy - shifted.accuracy
        if drop < CLAIMS_ACC_DROP_MIN:
            failures.append(
                f"{name}: {shift_name} no longer degrades accuracy "
                f"(drop {drop:.3f} < {CLAIMS_ACC_DROP_MIN}) — the shift "
                f"scenario lost its teeth")
    # uncertainty retention: the Bayesian model keeps far more predictive
    # entropy under shift than the frequentist point model (paper §V-B:
    # the mechanism by which CD-BFL avoids overconfident failures)
    if cd.entropy < cf.entropy + CLAIMS_ENTROPY_MARGIN:
        failures.append(
            f"uncertainty-retention claim broke under {shift_name}: cdbfl "
            f"entropy {cd.entropy:.4f} < cffl entropy {cf.entropy:.4f} + "
            f"{CLAIMS_ENTROPY_MARGIN}")
    # overconfidence onset: the shift turns the *frequentist* model
    # overconfident (confidence-accuracy gap rises by a clear margin)
    gap_rise = cf.overconf_gap - cf0.overconf_gap
    if gap_rise < CLAIMS_CFFL_GAP_RISE_MIN:
        failures.append(
            f"overconfidence-onset claim broke: cffl gap rose only "
            f"{gap_rise:+.4f} under {shift_name} "
            f"(< {CLAIMS_CFFL_GAP_RISE_MIN}) — Fig. 4's frequentist "
            f"overconfidence signal vanished")
    # raw ECE ordering: warning-only at reduced scale (see note above)
    if not (cd.ece <= cf.ece + CLAIMS_ECE_MARGIN):
        warnings.append(
            f"reduced-scale ECE ordering under {shift_name}: cdbfl ECE "
            f"{cd.ece:.4f} > cffl ECE {cf.ece:.4f} + {CLAIMS_ECE_MARGIN} "
            f"(known DESIGN.md §7/§10 deviation: the cold-posterior BMA "
            f"is underconfident at smoke scale; gated via the entropy and "
            f"overconfidence-onset claims instead)")
    return {
        "cells": cells,
        "failures": failures,
        "warnings": warnings,
        "claims": {
            "shift_scenario": shift_name,
            "shift_severity": shift_sev,
            "cdbfl_shift_ece": cd.ece,
            "cffl_shift_ece": cf.ece,
            "cdbfl_shift_entropy": cd.entropy,
            "cffl_shift_entropy": cf.entropy,
            "cdbfl_shift_gap": cd.overconf_gap,
            "cffl_shift_gap": cf.overconf_gap,
            "cffl_gap_rise": gap_rise,
            "cdbfl_acc_drop": cd0.accuracy - cd.accuracy,
            "cffl_acc_drop": cf0.accuracy - cf.accuracy,
        },
    }


# --------------------------------------------------------------------------
# Drift-recovery gate + unlearning oracle (DESIGN.md §15)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class DriftRecoverySpec:
    """A continual-training run probed for calibration recovery.

    A step drift of ``severity`` hits at ``onset`` (after the bank holds
    pre-drift posterior samples — the hard case), training continues on
    the drifted pool, and every ``probe_every`` rounds the *current*
    distribution's held-out cell is scored. The pre-drift steady state is
    the mean ECE of the post-burn-in, pre-onset probes; an *excursion* is
    the first post-onset probe whose ECE leaves the
    ``pre_ece + recover_eps`` band, and recovery is the first probe after
    the excursion that re-enters it. A run whose calibration never leaves
    the band recovers trivially (zero rounds) — the gate scenario is
    chosen so the drift actually bites (``day23_critical`` at full
    severity moves probe ECE ≈ 0.12 above the band at the claims seed).

    Pure data: a recovery run is a deterministic function of the spec, so the probe curve — and the gate verdict — is reproducible bit-for-bit.
    """
    scenario: str = "day23_critical"
    severity: float = 1.0
    schedule: str = "step"        # step | ramp (the drift-rate knob)
    ramp_rounds: int = 0          # ramp duration; 0 = abrupt step
    rounds: int = 90
    onset: int = 45
    probe_every: int = 5
    refresh_every: int = 5
    burn_in: int = 20
    # bank aging so the moving posterior sheds pre-drift samples: hard
    # window eviction after `window` rounds + exponential age discount
    window: int = 25
    decay: float = 0.9
    nodes: int = 5
    per_node: int = 24
    local_steps: int = 8
    minibatch: int = 10
    eta: float = 3e-3
    zeta: float = 0.3
    temperature: float = 0.2
    compressor: str = "topk"
    compress_ratio: float = 0.01
    topology: str = "full"
    eval_examples: int = 200
    eval_batch_size: int = 64
    seed: int = 0
    arch: str = "lenet-radar"
    recover_eps: float = 0.05


#: the claims gate's hard bound: cdbfl's calibration must be back within
#: ``recover_eps`` of its pre-drift steady state no later than this many
#: rounds after drift onset (DRIFT_CLAIMS_SPEC scale; observed 25 rounds
#: at the claims seed, vs 40 for the uncompressed dsgld baseline)
DRIFT_RECOVERY_MAX_ROUNDS = 30

DRIFT_CLAIMS_SPEC = DriftRecoverySpec()


def run_drift_recovery(spec: DriftRecoverySpec, algorithm: str = "cdbfl",
                       log=print) -> Dict[str, object]:
    """Train ``algorithm`` through the spec's drift; return the probe
    curve and the recovery summary.

    Returns ``{"probes": [...], "pre_ece", "onset", "excursion_round",
    "recovery_round", "rounds_to_recovery"}``. ``excursion_round`` is the
    first post-onset probe whose ECE leaves the ``recover_eps`` band
    (None when the drift never perturbs calibration — then
    ``rounds_to_recovery`` is 0). ``recovery_round`` is the first probe
    after the excursion back inside the band; None when calibration never
    re-enters it (the gate then fails). ``rounds_to_recovery`` counts
    from ``onset``, matching the claim "recovers within N rounds of
    drift onset".
    """
    from repro.config import ContinualConfig
    from repro.train import FedTrainer
    cfg = get_arch(spec.arch).reduced
    model = get_model(cfg)
    train = make_dataset(spec.nodes * spec.per_node, hw=cfg.input_hw,
                         day=1, seed=spec.seed)
    shards = partition_iid(train, spec.nodes, seed=spec.seed)
    cont = ContinualConfig(
        scenario=spec.scenario, schedule=spec.schedule,
        severity=spec.severity, onset=spec.onset,
        ramp_rounds=spec.ramp_rounds, refresh_every=spec.refresh_every,
        window=spec.window, decay=spec.decay, drift_seed=spec.seed)
    fed = FedConfig(
        num_nodes=spec.nodes, local_steps=spec.local_steps, eta=spec.eta,
        zeta=spec.zeta, rounds=spec.rounds, burn_in=spec.burn_in,
        compressor=spec.compressor, compress_ratio=spec.compress_ratio,
        topology=spec.topology, temperature=spec.temperature,
        algorithm=algorithm, seed=spec.seed,
    )
    tr = FedTrainer(model, fed, shards, minibatch=spec.minibatch,
                    seed=spec.seed, eval_batch_size=spec.eval_batch_size,
                    continual=cont)
    sched = tr._refresher.schedule
    probes: List[Dict[str, float]] = []
    done = 0
    while done < spec.rounds:
        n = min(spec.probe_every, spec.rounds - done)
        tr.run(rounds=n)
        done += n
        now = int(tr.state.round)
        sev = float(sched.severity_at(now - 1))
        ds = make_scenario_dataset(spec.scenario, sev, spec.eval_examples,
                                   hw=cfg.input_hw, seed=spec.seed + 90)
        rep = tr.eval_report(ds)
        probes.append({"round": float(now), "severity": sev,
                       "accuracy": rep.accuracy, "ece": rep.ece,
                       "entropy": rep.entropy})
        if log:
            log(f"  [{algorithm}] round {now:3d} sev={sev:.2f} "
                f"acc={rep.accuracy:.4f} ece={rep.ece:.4f}")
    pre = [p["ece"] for p in probes
           if spec.burn_in < p["round"] <= spec.onset]
    pre_ece = float(np.mean(pre)) if pre else float("nan")
    band = pre_ece + spec.recover_eps
    excursion_round = None
    recovery_round = None
    for p in probes:
        if p["round"] <= spec.onset or p["severity"] == 0.0:
            continue
        if excursion_round is None:
            if p["ece"] > band:
                excursion_round = int(p["round"])
        elif p["ece"] <= band:
            recovery_round = int(p["round"])
            break
    if excursion_round is None:
        rounds_to_recovery = 0        # calibration never left the band
    elif recovery_round is None:
        rounds_to_recovery = None     # left the band and never came back
    else:
        rounds_to_recovery = recovery_round - spec.onset
    return {
        "algorithm": algorithm,
        "probes": probes,
        "pre_ece": pre_ece,
        "onset": spec.onset,
        "excursion_round": excursion_round,
        "recovery_round": recovery_round,
        "rounds_to_recovery": rounds_to_recovery,
    }


def run_drift_claims(spec: DriftRecoverySpec = DRIFT_CLAIMS_SPEC,
                     max_rounds: int = DRIFT_RECOVERY_MAX_ROUNDS,
                     log=print) -> Dict[str, object]:
    """The drift-recovery claims gate: cdbfl must recover calibration
    within ``max_rounds`` of drift onset; the uncompressed dsgld baseline
    runs for comparison (reported, not gated — compression is the paper's
    variable, recovery is the claim)."""
    failures: List[str] = []
    out: Dict[str, object] = {"curves": {}}
    for algorithm in ("cdbfl", "dsgld"):
        res = run_drift_recovery(spec, algorithm=algorithm, log=log)
        out["curves"][algorithm] = res
        if algorithm == "cdbfl":
            if res["rounds_to_recovery"] is None:
                failures.append(
                    f"drift-recovery claim broke: cdbfl ECE never returned "
                    f"within {spec.recover_eps} of the pre-drift steady "
                    f"state {res['pre_ece']:.4f} after onset at round "
                    f"{spec.onset}")
            elif res["rounds_to_recovery"] > max_rounds:
                failures.append(
                    f"drift-recovery claim broke: cdbfl took "
                    f"{res['rounds_to_recovery']} rounds to recover "
                    f"calibration (> {max_rounds})")
    out["failures"] = failures
    out["claims"] = {
        "drift_scenario": spec.scenario,
        "drift_severity": spec.severity,
        "drift_onset": spec.onset,
        "cdbfl_pre_ece": out["curves"]["cdbfl"]["pre_ece"],
        "cdbfl_rounds_to_recovery":
            out["curves"]["cdbfl"]["rounds_to_recovery"],
        "dsgld_rounds_to_recovery":
            out["curves"]["dsgld"]["rounds_to_recovery"],
    }
    return out


#: unlearn-vs-retrain oracle tolerances (DESIGN.md §15). Unlearning
#: removes the node's chain from the predictive mixture and zeroes its
#: control variates, but cannot rewind the influence its past gossip had
#: on the surviving chains — the residual discrepancy against a true
#: retrain-without-the-node is bounded by these (observed ≈ 0.05 acc /
#: 0.022 ECE at the oracle seed; asserted in tests/test_unlearn.py).
UNLEARN_ACC_TOL = 0.10
UNLEARN_ECE_TOL = 0.06


def run_unlearn_oracle(spec: MatrixSpec = CLAIMS_SPEC,
                       scenario: str = "clean", severity: float = 0.0,
                       log=print) -> Dict[str, object]:
    """Unlearn the last node and compare against the retrain oracle.

    Trains cdbfl on K nodes, unlearns node K-1, and retrains from scratch
    on the same first K-1 shards with ``num_nodes=K-1``. The *last* node
    is the oracle target so every surviving node keeps its global id —
    identical per-node PRNG streams and data shards; all residual
    difference is the removed node's gossip influence plus the Ω-mixing
    renormalization, which the tolerances bound.
    """
    from repro.train import FedTrainer
    cfg = get_arch(spec.arch).reduced
    model = get_model(cfg)
    train = make_dataset(spec.nodes * spec.per_node, hw=cfg.input_hw,
                         day=1, seed=spec.seed)
    shards = partition_iid(train, spec.nodes, seed=spec.seed)
    ds = make_scenario_dataset(scenario, severity, spec.eval_examples,
                               hw=cfg.input_hw, seed=spec.seed + 90)

    def build(num_nodes: int, node_shards):
        fed = FedConfig(
            num_nodes=num_nodes, local_steps=spec.local_steps, eta=spec.eta,
            zeta=spec.zeta, rounds=spec.rounds,
            burn_in=int(spec.rounds * spec.burn_in_frac),
            compressor=spec.compressor, compress_ratio=spec.compress_ratio,
            topology=spec.topology, temperature=spec.temperature,
            algorithm="cdbfl", seed=spec.seed,
        )
        return FedTrainer(model, fed, node_shards, minibatch=spec.minibatch,
                          seed=spec.seed,
                          eval_batch_size=spec.eval_batch_size)

    target = spec.nodes - 1
    tr = build(spec.nodes, shards)
    tr.run(rounds=spec.rounds)
    tr.unlearn(target)
    rep_unlearn = tr.eval_report(ds)

    oracle = build(spec.nodes - 1, shards[:target])
    oracle.run(rounds=spec.rounds)
    rep_oracle = oracle.eval_report(ds)

    d_acc = abs(rep_unlearn.accuracy - rep_oracle.accuracy)
    d_ece = abs(rep_unlearn.ece - rep_oracle.ece)
    if log:
        log(f"  unlearn(node {target}): acc={rep_unlearn.accuracy:.4f} "
            f"ece={rep_unlearn.ece:.4f} | retrain oracle: "
            f"acc={rep_oracle.accuracy:.4f} ece={rep_oracle.ece:.4f} | "
            f"|Δacc|={d_acc:.4f} |Δece|={d_ece:.4f}")
    return {
        "target": target,
        "unlearn": rep_unlearn,
        "oracle": rep_oracle,
        "delta_accuracy": d_acc,
        "delta_ece": d_ece,
        "within_tolerance": bool(d_acc <= UNLEARN_ACC_TOL
                                 and d_ece <= UNLEARN_ECE_TOL),
    }
