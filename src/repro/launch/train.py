"""Distributed CD-BFL training driver (runs on whatever devices exist).

On the production mesh the federated nodes live on a mesh axis; on this CPU
container it degrades to a 1-device mesh and the node axis is vmapped — the
same jitted round function either way (DESIGN.md §3). The same FedConfig
runs in three execution modes:

* ``--mesh 1`` (default): single-device, node axis vmapped.
* ``--mesh N --engine scan``: GSPMD-auto — state is placed with the node
  axis sharded over the ``--fed-axis`` mesh axis and the compiler inserts
  the gossip collectives.
* ``--mesh N --engine shard``: explicit collectives — the scan-fused
  super-round runs inside ``shard_map`` and the Ω-mixing is hand-lowered
  to ``lax.ppermute`` neighbor exchange (DESIGN.md §4), with cross-shard
  bytes reported separately from intra-shard bytes.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --trim --nodes 4 --rounds 20 --local-steps 4 --seq 128 --batch 4

    # 8 federated nodes on 4 forced CPU shards, explicit ppermute gossip
    PYTHONPATH=src python -m repro.launch.train --arch lenet-radar --trim \
        --nodes 8 --mesh 4 --engine shard --rounds 20

``--trim`` shrinks the model to the reduced config, for CPU runs only;
on a TPU the model runs at its published width. On CPU, ``--mesh N``
forces N host devices — it must therefore run before anything initializes
the JAX backend (:func:`main` handles that; see ``repro.launch.xla_flags``).

:func:`main` takes an argument list and returns a :class:`TrainRun`, so a
caller such as ``chip_smoke.py`` drives this exact path and checks what it
produced.
"""
from __future__ import annotations

import argparse
import time
from typing import Any, List, NamedTuple, Optional, Sequence

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.xla_flags import force_host_device_count


class TrainRun(NamedTuple):
    """What one :func:`main` run leaves behind."""
    state: Any                  # final FedState (leaves (K, ...))
    losses: List[float]         # per-round mean loss, every round
    consensus: List[float]      # per-round consensus error, every round
    compressor: Any             # the codec the rounds encoded with
    bank: Any                   # (S, K, ...) posterior samples, or None


def _parse_args(argv: Optional[Sequence[str]] = None):
    # jax-free import: topology pulls in numpy + repro.config only
    from repro.core.topology import GRAPHS

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--trim", action="store_true", help="use reduced config")
    ap.add_argument("--algorithm", default="cdbfl",
                    choices=["cdbfl", "dsgld", "cffl"])
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4, help="per-node minibatch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--eta", type=float, default=1e-4)
    ap.add_argument("--zeta", type=float, default=0.3)
    ap.add_argument("--topology", default="ring", choices=list(GRAPHS))
    ap.add_argument("--degree", type=int, default=4,
                    help="k_regular neighbor count")
    ap.add_argument("--edge-prob", type=float, default=0.3,
                    help="erdos_renyi link probability")
    ap.add_argument("--radius", type=float, default=0.45,
                    help="geometric radio range (unit square)")
    ap.add_argument("--link-failure", type=float, default=0.0,
                    help="per-round per-link dropout probability")
    ap.add_argument("--gossip-pairs", type=int, default=0,
                    help=">0: activate only this many matchings per round")
    ap.add_argument("--topo-seed", type=int, default=0,
                    help="graph-sampling seed (erdos_renyi/geometric)")
    ap.add_argument("--transport", action="store_true",
                    help="frame the wire payloads (MTU fragmentation + "
                         "header/airtime accounting) even at zero loss")
    ap.add_argument("--mtu", type=int, default=256,
                    help="transport frame MTU in bytes (8-byte header)")
    ap.add_argument("--erasure", type=float, default=0.0,
                    help=">0: per-frame Bernoulli erasure rate (implies "
                         "--transport; error feedback re-offers lost mass)")
    ap.add_argument("--loss-model", default="bernoulli",
                    choices=["bernoulli", "gilbert"],
                    help="frame-loss process (gilbert: bursty episodes)")
    ap.add_argument("--snr-db", type=float, default=None,
                    help="mean link SNR: enables the Rayleigh per-link "
                         "outage model on the gossip schedule")
    ap.add_argument("--snr-spread-db", type=float, default=0.0,
                    help="per-node lognormal shadowing std dev (dB)")
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="ablation: sender's control sequence absorbs the "
                         "full delta even when frames were lost")
    ap.add_argument("--arq", action="store_true",
                    help="selective-repeat retransmission of lost frames "
                         "(implies --transport; see --max-retries)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="ARQ retransmit attempts per frame per round")
    ap.add_argument("--arq-backoff", type=float, default=0.0,
                    help="base retransmit backoff in seconds (doubles per "
                         "attempt; charged against the airtime budget)")
    ap.add_argument("--toa", action="store_true",
                    help="LoRa time-on-air airtime accounting (SX127x "
                         "formula) instead of the flat PHY rate "
                         "(implies --transport)")
    ap.add_argument("--sf", type=int, default=7,
                    help="LoRa spreading factor 6-12 (with --toa)")
    ap.add_argument("--duty-cycle", type=float, default=1.0,
                    help="fraction of the round period the radio may "
                         "transmit (budget = duty-cycle x round period)")
    ap.add_argument("--round-period-s", type=float, default=0.0,
                    help=">0: wall-clock round period bounding the ARQ "
                         "airtime budget; frames over budget are abandoned "
                         "to the CHOCO residual")
    ap.add_argument("--straggler-prob", type=float, default=0.0,
                    help=">0: barrier-free rounds — each node skips a "
                         "round with this probability (stale-weighted "
                         "mixing carries its last state)")
    ap.add_argument("--dead-node", action="append", default=[],
                    metavar="NODE:DIE[:REJOIN]",
                    help="node death timeline, e.g. '2:30' (node 2 dies at "
                         "round 30) or '2:30:60' (rejoins at 60); repeatable")
    ap.add_argument("--compressor", default="block_topk")
    ap.add_argument("--pipeline", default="",
                    help="codec pipeline DSL, e.g. 'block_topk|qsgd' "
                         "(overrides --compressor; stages from "
                         "core/compression.py)")
    ap.add_argument("--ratio", type=float, default=0.01)
    ap.add_argument("--fused-compress", action="store_true",
                    help="fuse compress-encode into the update: Q(θ−v) is "
                         "computed straight from (θ, v) in Pallas so the "
                         "dense residual never materializes in HBM "
                         "(DESIGN.md §13); bitwise-equal to the two-pass "
                         "path under jit")
    ap.add_argument("--layer-pipelines", default="",
                    help="per-layer codec overrides, "
                         "'pattern=pipeline;pattern=pipeline' — first "
                         "substring match on the param path wins, '*' "
                         "matches all, e.g. 'embed=block_topk;"
                         "*=block_topk|qsgd'")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--bank-capacity", type=int, default=0,
                    help=">0: keep a device-resident posterior sample bank "
                         "of this capacity (cdbfl/dsgld) and snapshot it to "
                         "--ckpt-dir at every --eval-every boundary — the "
                         "train -> serve pipeline (launch.serve hot-swaps "
                         "the snapshots in)")
    ap.add_argument("--burn-in", type=int, default=-1,
                    help="rounds before bank admission (-1: rounds // 2)")
    ap.add_argument("--thin", type=int, default=1,
                    help="bank admission stride after burn-in")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--engine", default="scan",
                    choices=["scan", "host", "shard"],
                    help="scan: chunked lax.scan super-rounds (default; "
                         "GSPMD-auto when --mesh > 1); host: per-round "
                         "dispatch reference loop; shard: shard_map + "
                         "explicit ppermute gossip (needs --mesh > 1)")
    ap.add_argument("--mesh", type=int, default=1,
                    help="shards on the federated mesh axis (must divide "
                         "--nodes); >1 forces that many host devices on CPU")
    ap.add_argument("--fed-axis", default="fed",
                    help="mesh axis name carrying the federated node axis")
    ap.add_argument("--pool", type=int, default=64,
                    help="per-node synthetic sequence pool size (rounds "
                         "sample minibatches from it on device)")
    ap.add_argument("--drift", default="",
                    help="scenario family whose severity drifts over "
                         "training (lenet pools only; empty = static "
                         "data). The schedule is pure in (seed, round) — "
                         "see --drift-*/--refresh-* and DESIGN.md §15")
    ap.add_argument("--drift-kind", default="step",
                    choices=["constant", "step", "ramp", "cyclic"],
                    help="severity trajectory shape")
    ap.add_argument("--drift-severity", type=float, default=0.8,
                    help="plateau/peak severity of the drift")
    ap.add_argument("--drift-base", type=float, default=0.0,
                    help="pre-onset severity (base == severity never "
                         "leaves the original pool)")
    ap.add_argument("--drift-onset", type=int, default=0,
                    help="first drifted round (step/ramp/cyclic)")
    ap.add_argument("--drift-ramp-rounds", type=int, default=0,
                    help="ramp duration in rounds (kind=ramp)")
    ap.add_argument("--drift-period", type=int, default=0,
                    help="cycle period in rounds (kind=cyclic)")
    ap.add_argument("--drift-seed", type=int, default=0,
                    help="drift-synthesis stream seed")
    ap.add_argument("--refresh-every", type=int, default=5,
                    help="drift phase quantization: rounds between "
                         "training-pool refreshes")
    ap.add_argument("--refresh-window", type=int, default=0,
                    help=">0: evict posterior-bank samples older than "
                         "this many rounds from the BMA mixture "
                         "(continual bank aging, DESIGN.md §15)")
    ap.add_argument("--refresh-decay", type=float, default=1.0,
                    help="<1: exponential age discount on bank-sample "
                         "BMA weights")
    ap.add_argument("--eval-every", type=int, default=0,
                    help=">0: score the consensus model every N rounds "
                         "through the fused eval engine (DESIGN.md §10)")
    ap.add_argument("--eval-scenario", default="clean",
                    help="shift family for the in-training eval set "
                         "(lenet pools; see repro.data.scenarios)")
    ap.add_argument("--eval-severity", type=float, default=1.0)
    ap.add_argument("--eval-examples", type=int, default=128)
    return ap.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> TrainRun:
    # flags first: --mesh N needs N host devices before JAX initializes
    args = _parse_args(argv)
    if args.mesh > 1:
        force_host_device_count(args.mesh)
    enable_compile_cache()
    if args.engine == "shard" and args.mesh < 2:
        raise SystemExit("--engine shard needs --mesh >= 2")
    if args.nodes % max(args.mesh, 1):
        raise SystemExit(f"--nodes {args.nodes} must divide evenly over "
                         f"--mesh {args.mesh}")

    import jax
    import numpy as np

    from repro.checkpoint import save_bank, save_checkpoint
    from repro.config import FedConfig, TopologyConfig, get_arch
    from repro.core import (ShardContext, build_topology, init_fed_state,
                            make_compressor, make_round_fn,
                            parse_layer_rules)
    from repro.core.gossip import plan_mixer
    from repro.core.topology import dense_wire_bytes
    from repro.data.partition import DeviceShards
    from repro.data.synthetic_lm import markov_tokens
    from repro.models import get_model
    from repro.train.engine import make_engine

    spec = get_arch(args.arch)
    cfg = spec.reduced if args.trim else spec.config
    model = get_model(cfg)
    topo_cfg = TopologyConfig(
        graph=args.topology, degree=args.degree, edge_prob=args.edge_prob,
        radius=args.radius, seed=args.topo_seed,
        link_failure_prob=args.link_failure, gossip_pairs=args.gossip_pairs,
    )
    tcfg = None
    if (args.transport or args.erasure > 0 or args.snr_db is not None
            or args.arq or args.toa):
        from repro.config import TransportConfig
        tcfg = TransportConfig(
            mtu=args.mtu, erasure=args.erasure, loss_model=args.loss_model,
            snr_db=args.snr_db, snr_spread_db=args.snr_spread_db,
            error_feedback=not args.no_error_feedback,
            arq=args.arq, max_retries=args.max_retries,
            arq_backoff_s=args.arq_backoff,
            toa=args.toa, sf=args.sf, duty_cycle=args.duty_cycle,
            round_period_s=args.round_period_s)
    pcfg = None
    if args.straggler_prob > 0 or args.dead_node:
        from repro.config import ParticipationConfig
        dead = []
        for spec_str in args.dead_node:
            parts = [int(p) for p in spec_str.split(":")]
            if len(parts) == 2:
                parts.append(-1)
            if len(parts) != 3:
                raise SystemExit(f"--dead-node {spec_str!r}: want "
                                 f"NODE:DIE[:REJOIN]")
            dead.append(tuple(parts))
        pcfg = ParticipationConfig(straggler_prob=args.straggler_prob,
                                   dead=tuple(dead))
    fed = FedConfig(
        num_nodes=args.nodes, local_steps=args.local_steps,
        eta=args.eta, zeta=args.zeta, topology=args.topology,
        topology_cfg=topo_cfg,
        compressor=args.compressor, pipeline=args.pipeline,
        compress_ratio=args.ratio,
        fused_compress=args.fused_compress,
        layer_pipelines=parse_layer_rules(args.layer_pipelines),
        algorithm=args.algorithm,
        transport=tcfg,
        participation=pcfg,
    )
    topo = build_topology(topo_cfg, fed.num_nodes)
    omega = topo.omega
    comp = make_compressor(fed)
    # execution substrate: single device, GSPMD-auto, or explicit collectives
    mesh = None
    shard_ctx = None
    if args.mesh > 1:
        from repro.launch.mesh import make_fed_mesh
        mesh = make_fed_mesh(args.mesh, fed_axis=args.fed_axis)
        if args.engine == "shard":
            shard_ctx = ShardContext(args.fed_axis, args.mesh)
    round_fn = make_round_fn(args.algorithm, model.loss, fed, omega,
                             comp, data_scale=1.0, shard_ctx=shard_ctx)

    key = jax.random.PRNGKey(fed.seed)
    params0 = model.init(key)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params0))
    state = init_fed_state(params0, fed, key=key)
    # dsgld gossips uncompressed θ; the compressed algorithms ship Q(Δθ)
    wire = (n_params * 4 if args.algorithm == "dsgld"
            else comp.wire_bytes(params0))
    # report exactly the lowering make_mixer will execute (same decision fn;
    # an SNR outage model forces the time-varying schedule)
    mode, sched = plan_mixer(omega, topo_cfg,
                             force_tv=tcfg is not None
                             and tcfg.snr_db is not None)
    n_perms = sched.num_perms if sched else 0
    if mode.startswith("schedule"):
        # expected payloads/round: gossip-pair sampling activates only
        # `pairs` matchings, and each surviving edge beats link dropout
        active = (args.gossip_pairs if 0 < args.gossip_pairs < n_perms
                  else n_perms)
        gossip_wire = active * wire * (1.0 - args.link_failure)
    else:
        gossip_wire = dense_wire_bytes(fed.num_nodes, wire)
    q_name = fed.pipeline or fed.compressor
    print(f"arch={cfg.name} params={n_params/1e6:.2f}M nodes={fed.num_nodes} "
          f"L={fed.local_steps} Q={q_name}@{fed.compress_ratio} "
          f"wire={wire/1e6:.3f}MB/node/round "
          f"(dense {n_params*4/1e6:.1f}MB, saving "
          f"{100*(1-wire/(n_params*4)):.1f}%)")
    if hasattr(comp, "formula_bytes") and args.algorithm != "dsgld":
        formula = comp.formula_bytes(params0)
        print(f"wire accounting: measured={wire} B/node (packed payload) "
              f"formula={formula} B/node "
              f"(x{wire/max(formula, 1):.3f} byte-alignment)")
    print(f"topology={topo.describe()} |λ2|={topo.lambda2:.4f} "
          f"mixer={mode} matchings={n_perms} "
          f"gossip_wire={gossip_wire/1e6:.3f}MB/node/round "
          f"(dense all-gather "
          f"{dense_wire_bytes(fed.num_nodes, wire)/1e6:.3f}MB)"
          + (f" link_failure={args.link_failure}" if args.link_failure else "")
          + (f" gossip_pairs={args.gossip_pairs}" if args.gossip_pairs else ""))
    if tcfg is not None:
        print(f"transport: mtu={tcfg.mtu}B (+8B header/frame) "
              f"loss={tcfg.loss_model}@{tcfg.erasure:g} "
              + (f"snr={tcfg.snr_db:g}±{tcfg.snr_spread_db:g}dB "
                 if tcfg.snr_db is not None else "")
              + f"error_feedback={'on' if tcfg.error_feedback else 'OFF'}"
              + (f" arq=selective-repeat x{tcfg.max_retries}"
                 + (f" backoff={tcfg.arq_backoff_s:g}s"
                    if tcfg.arq_backoff_s else "")
                 if tcfg.arq else "")
              + (f" toa=SF{tcfg.sf}/{tcfg.bw_hz/1e3:g}kHz" if tcfg.toa
                 else ""))
        if tcfg.round_period_s > 0:
            print(f"airtime budget: {tcfg.duty_cycle:g} duty x "
                  f"{tcfg.round_period_s:g}s round = "
                  f"{tcfg.duty_cycle * tcfg.round_period_s:g}s/node/round "
                  f"(over-budget frames abandoned to the residual)")
    if pcfg is not None:
        print(f"participation: straggler_prob={pcfg.straggler_prob:g} "
              f"dead={list(pcfg.dead) or 'none'} "
              f"(barrier-free rounds, stale-weighted mixing)")

    # per-node synthetic pool, resident on device; rounds gather minibatch
    # index tensors from the round key inside the engine (no per-round H2D)
    if cfg.family == "lenet":
        from repro.data.partition import partition_iid
        from repro.data.radar import make_dataset
        ds = make_dataset(fed.num_nodes * args.pool, hw=cfg.input_hw,
                          day=1, seed=fed.seed)
        pool = partition_iid(ds, fed.num_nodes, seed=fed.seed)
    else:
        pool = [
            {"tokens": markov_tokens(args.pool, args.seq, cfg.vocab_size,
                                     seed=fed.seed, node=k_node)}
            for k_node in range(fed.num_nodes)
        ]
    dshards = DeviceShards.from_shards(pool)
    # streaming drift: the training pool follows a severity schedule, the
    # engines re-draw it at phase boundaries via set_shards (DESIGN.md §15)
    refresher = cont = None
    if args.drift:
        if cfg.family != "lenet":
            raise SystemExit("--drift needs a lenet pool (the scenario "
                             "registry synthesizes radar maps, not tokens)")
        if args.mesh > 1 and args.engine != "shard":
            raise SystemExit("--drift with --mesh > 1 needs --engine shard "
                             "(GSPMD-auto placement would be lost on pool "
                             "refresh)")
        from repro.config import ContinualConfig
        from repro.train.drift import make_refresher
        cont = ContinualConfig(
            scenario=args.drift, schedule=args.drift_kind,
            severity=args.drift_severity, base_severity=args.drift_base,
            onset=args.drift_onset, ramp_rounds=args.drift_ramp_rounds,
            period=args.drift_period, refresh_every=args.refresh_every,
            drift_seed=args.drift_seed, window=args.refresh_window,
            decay=args.refresh_decay)
        refresher = make_refresher(cont, dshards)
        print(f"drift: {args.drift} kind={args.drift_kind} "
              f"severity={args.drift_base:g}->{args.drift_severity:g} "
              f"onset={args.drift_onset} refresh_every={args.refresh_every}"
              + (f" window={args.refresh_window}" if args.refresh_window
                 else "")
              + (f" decay={args.refresh_decay:g}"
                 if args.refresh_decay < 1.0 else ""))
    if mesh is not None and args.engine != "shard":
        # GSPMD-auto: same scan engine, node axis sharded by placement —
        # the compiler inserts the gossip collectives (DESIGN.md §3)
        from repro.launch.sharding import place_fed_state
        state = place_fed_state(state, mesh, args.fed_axis)
        dshards = dshards.with_sharding(mesh, args.fed_axis)
    # posterior bank: the serving plane's sample source (DESIGN.md §14)
    bank_cfg = bank_state = None
    if args.bank_capacity > 0 and args.algorithm in ("cdbfl", "dsgld"):
        from repro.core.posterior import DeviceSampleBank
        burn = args.burn_in if args.burn_in >= 0 else args.rounds // 2
        bank_cfg = DeviceSampleBank(burn_in=burn,
                                    capacity=args.bank_capacity,
                                    thin=args.thin)
    engine = make_engine(args.engine, round_fn, dshards, fed.local_steps,
                         args.batch, bank=bank_cfg,
                         chunk=args.log_every or 64,
                         mesh=mesh, fed_axis=args.fed_axis)
    if bank_cfg is not None:
        # host engine keeps the mutable list bank; scan/shard carry the
        # device ring buffer through the fused rounds
        bank_state = (engine.make_bank() if args.engine == "host"
                      else bank_cfg.init(state.params))
        print(f"posterior bank: capacity={args.bank_capacity} "
              f"burn_in={bank_cfg.burn_in} thin={bank_cfg.thin}"
              + (f" snapshots -> {args.ckpt_dir}" if args.ckpt_dir else ""))
    if args.mesh > 1:
        sub = ("shard_map + ppermute collectives" if args.engine == "shard"
               else "GSPMD-auto (sharded placement)")
        print(f"mesh={args.mesh}x{args.fed_axis!r} "
              f"({fed.num_nodes // args.mesh} nodes/shard) substrate={sub}")

    # periodic in-training evaluation through the fused eval engine: the
    # consensus (node-averaged point) model is scored on a held-out set
    # every --eval-every rounds, same compiled path as launch.evaluate
    eval_engine = eval_ds = None
    if args.eval_every > 0:
        from repro.eval.engine import (ScanEvalEngine, ShardEvalEngine,
                                       as_stacked, lm_apply_fn)
        if cfg.family == "lenet":
            from repro.data.scenarios import make_scenario_dataset
            eval_ds = make_scenario_dataset(
                args.eval_scenario, args.eval_severity, args.eval_examples,
                hw=cfg.input_hw, seed=fed.seed + 90)
            apply_fn = lambda p, b: model.logits(p, b)
        else:
            held = markov_tokens(args.eval_examples, args.seq,
                                 cfg.vocab_size, seed=fed.seed,
                                 node=fed.num_nodes)   # unseen node stream
            eval_ds = {"tokens": held, "y": np.asarray(held)[:, 1:]}
            apply_fn = lm_apply_fn(model)
        if args.engine == "shard":
            eval_engine = ShardEvalEngine(apply_fn, mesh, args.fed_axis)
        else:
            eval_engine = ScanEvalEngine(apply_fn)

    last_time, last_round = time.perf_counter(), 0

    def log_cb(t, loss, cons):
        """One line per log interval; its s/round covers that interval
        only, so compilation shows in the first line alone."""
        nonlocal last_time, last_round
        now = time.perf_counter()
        per_round = (now - last_time) / max(t - last_round, 1)
        last_time, last_round = now, t
        print(f"round {t:4d} loss={loss:.4f} consensus={cons:.3e} "
              f"({per_round:.2f}s/round)")
    key = jax.random.fold_in(key, 1)

    def bank_stacked():
        """(S, K, ...) posterior samples, or None while still empty."""
        if bank_cfg is None or bank_state is None:
            return None
        if hasattr(bank_state, "samples"):          # host SampleBank
            if not bank_state.samples:
                return None
            import jax.numpy as jnp
            return jax.tree.map(lambda *xs: jnp.stack(xs),
                                *bank_state.samples)
        if not bank_cfg.length(bank_state):
            return None
        return bank_cfg.stacked(bank_state)

    def bank_weights(now: int):
        """Age-discounted BMA weights under --refresh-window/--refresh-
        decay (None = uniform, the pre-continual path)."""
        if cont is None or not cont.ages or bank_cfg is None \
                or bank_state is None:
            return None
        from repro.core.posterior import bank_age_weights
        rounds_seen = (bank_state.rounds if hasattr(bank_state, "samples")
                       else bank_cfg.rounds_list(bank_state))
        if not len(rounds_seen):
            return None
        return bank_age_weights(rounds_seen, now, window=cont.window,
                                decay=cont.decay)

    segment = args.eval_every if args.eval_every > 0 else args.rounds
    done = 0
    all_losses: List[float] = []
    all_cons: List[float] = []
    stacked_bank = None
    while done < args.rounds:
        n = min(segment, args.rounds - done)
        subsegs = (list(refresher.segments(done, n))
                   if refresher is not None else [(done, n)])
        for s0, m in subsegs:
            if refresher is not None:
                refresher.refresh(engine, s0)
            state, key, bank_state, losses, cons = engine.run(
                state, key, bank_state, m, t0=s0,
                log_every=args.log_every, log_cb=log_cb)
            all_losses.extend(losses)
            all_cons.extend(cons)
        done += n
        stacked_bank = bank_stacked()
        if eval_engine is not None:
            # BMA over the posterior bank once it has samples; the
            # consensus point model before burn-in
            stacked = (stacked_bank if stacked_bank is not None
                       else as_stacked(state.params))
            # under drift, score the *current* distribution's held-out
            # cell (what "calibration recovers" means in DESIGN.md §15)
            eval_name, eval_sev, ds_now = (args.eval_scenario,
                                           args.eval_severity, eval_ds)
            if refresher is not None:
                eval_name = args.drift
                eval_sev = float(refresher.schedule.severity_at(done - 1))
                ds_now = refresher.eval_dataset(done - 1,
                                                args.eval_examples,
                                                seed=fed.seed + 90)
            w = (bank_weights(done)
                 if stacked_bank is not None else None)
            if args.engine == "shard":
                rep = eval_engine.evaluate(stacked, ds_now, weights=w)
            else:
                rep = eval_engine.evaluate(stacked, ds_now, node_axis=1,
                                           weights=w)
            s = jax.tree.leaves(stacked)[0].shape[0]
            print(f"eval  round {done:4d} [{eval_name}"
                  f"@{eval_sev:g}] S={s} acc={rep.accuracy:.4f} "
                  f"ece={rep.ece:.4f} nll={rep.nll:.4f} "
                  f"gap={rep.overconf_gap:+.4f}"
                  + (" aged" if w is not None else ""))
        if args.ckpt_dir and stacked_bank is not None:
            # atomic publish: a concurrently polling server (launch.serve
            # --poll-s) hot-swaps this snapshot in without ever seeing a
            # half-written file
            path = save_bank(args.ckpt_dir, done,
                             jax.tree.map(np.asarray, stacked_bank),
                             metadata={"arch": cfg.name, "round": done})
            print(f"bank snapshot: {path} "
                  f"(S={jax.tree.leaves(stacked_bank)[0].shape[0]})")
    offered = getattr(engine, "last_offered_history", [])
    if offered and float(offered[-1]) > 0:
        delivered = float(engine.last_delivered_history[-1])
        frac = delivered / float(offered[-1])
        print(f"transport accounting: offered "
              f"{float(offered[-1]):.0f}B/node/round, delivered "
              f"{delivered:.0f}B ({100 * frac:.1f}%), airtime "
              f"{1e3 * float(engine.last_airtime_history[-1]):.2f}ms, "
              f"energy {1e3 * float(engine.last_energy_history[-1]):.2f}mJ")
        retrans = getattr(engine, "last_retransmit_history", [])
        if retrans and (tcfg is not None and tcfg.arq):
            print(f"arq accounting: {float(retrans[-1]):.2f} "
                  f"retransmits/node/round, "
                  f"{float(engine.last_abandoned_history[-1]):.0f}B "
                  f"abandoned at budget exhaustion")
    part = getattr(engine, "last_participation_history", [])
    if pcfg is not None and len(part):
        rates = np.asarray(part, np.float64).mean(axis=0)
        print("participation rates: "
              + " ".join(f"n{i}={r:.2f}" for i, r in enumerate(rates))
              + f" (mean {rates.mean():.2f})")
    cross = getattr(engine, "last_cross_history", [])
    if cross and cross[-1] > 0:
        # only the explicit-collective path accounts its ppermute traffic;
        # GSPMD-auto moves bytes too but the compiler owns the schedule
        print(f"cross-shard gossip traffic: {cross[-1]/1e6:.3f}MB/node/round "
              f"(intra-shard exchange + compute stay on-shard)")
    if args.ckpt_dir:
        path = save_checkpoint(args.ckpt_dir, args.rounds, state.params,
                               metadata={"arch": cfg.name, "fed": vars(args)})
        print("saved", path)
    return TrainRun(state, all_losses, all_cons, comp, stacked_bank)


if __name__ == "__main__":
    main()
