"""Uncertainty-aware serving CLI over ``repro.serve`` (DESIGN.md §14).

Thin argparse shim over :class:`repro.config.ServeConfig` — flags map 1:1
onto config fields, every behavior lives in the engine (the same
config-over-flags pattern as ``TransportConfig`` / ``ParticipationConfig``).

Loads a posterior bank snapshot directory written by ``launch.train
--bank-capacity ... --ckpt-dir ...`` (or synthesizes a jittered bank when
none is given), serves a batch of requests through the continuous-batching
engine and reports throughput, tail latency and the abstain rate. With
``--follow-snapshots`` the engine hot-swaps through every snapshot in the
directory *while requests are in flight*; ``--poll-s`` additionally polls
for snapshots appearing live (a concurrently running trainer).

    # classify: radar posterior, 32 requests, entropy gate at 1.2 nats
    PYTHONPATH=src python -m repro.launch.serve --arch lenet-radar --trim \
        --requests 32 --entropy-threshold 1.2

    # BMA decode with the sample axis sharded over 8 host devices
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --trim \
        --mode decode --mesh 8 --samples 8 --requests 16

``--trim`` is for CPU runs only. :func:`main` takes an argument list and
returns a :class:`ServeRun`, so a caller such as ``chip_smoke.py`` drives
this exact path and checks the responses.
"""
from __future__ import annotations

import argparse
import time
from typing import Any, List, NamedTuple, Optional, Sequence

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.xla_flags import force_host_device_count


class ServeRun(NamedTuple):
    """What one :func:`main` run served."""
    responses: List[Any]        # ServeResponse, sorted by request id
    requests: List[Any]         # the ServeRequests, in submission order
    bank: Any                   # the posterior bank the engine started from
    node_axis: Optional[int]    # 1 for (S, K, ...) trainer banks
    recompiles: int             # compiles after the warmup request


def _parse_args(argv: Optional[Sequence[str]] = None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lenet-radar")
    ap.add_argument("--trim", action="store_true", help="use reduced config")
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "classify", "decode"],
                    help="auto: classify for classifier families, decode "
                         "for LM families")
    ap.add_argument("--ckpt-dir", default=None,
                    help="load the posterior bank snapshots written by "
                         "launch.train (bank_*.npz); no dir -> synthetic "
                         "jittered bank")
    ap.add_argument("--samples", type=int, default=4,
                    help="synthetic posterior size when no --ckpt-dir")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    # ServeConfig fields (thin shim: one flag per field)
    ap.add_argument("--slots", type=int, default=8,
                    help="slot-table width (the fixed compiled batch)")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--entropy-threshold", type=float, default=float("inf"),
                    help="abstain (route-to-human) above this predictive "
                         "entropy in nats")
    ap.add_argument("--poll-s", type=float, default=0.0,
                    help=">0: poll --ckpt-dir for new bank snapshots "
                         "between steps and hot-swap them in")
    ap.add_argument("--mesh", type=int, default=0,
                    help=">1: shard the posterior sample axis over this "
                         "many host devices (ensemble parallelism)")
    ap.add_argument("--ensemble-axis", default="ens")
    ap.add_argument("--follow-snapshots", action="store_true",
                    help="start from the oldest bank snapshot and hot-swap "
                         "through the rest while requests are in flight")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: assert zero recompiles after warmup and "
                         "print the response fields")
    return ap.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> ServeRun:
    args = _parse_args(argv)
    if args.mesh > 1:
        force_host_device_count(args.mesh)
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import load_bank
    from repro.config import ServeConfig, get_arch
    from repro.eval.engine import lm_apply_fn
    from repro.models import get_model
    from repro.serve import ClassifyEngine, DecodeEngine, ServeRequest

    spec = get_arch(args.arch)
    cfg = spec.reduced if args.trim else spec.config
    model = get_model(cfg)
    mode = args.mode
    if mode == "auto":
        mode = "classify" if model.decode_step is None else "decode"

    scfg = ServeConfig(
        slots=args.slots, max_len=args.max_len,
        max_new_tokens=args.max_new_tokens, temperature=args.temperature,
        entropy_threshold=args.entropy_threshold,
        hot_swap_poll_s=args.poll_s,
        ensemble_axis=args.ensemble_axis if args.mesh > 1 else "")
    mesh = None
    if args.mesh > 1:
        from repro.launch.mesh import make_fed_mesh
        mesh = make_fed_mesh(args.mesh, fed_axis=args.ensemble_axis)

    key = jax.random.PRNGKey(args.seed)
    params0 = model.init(key)
    base_ndims = jax.tree.map(lambda x: x.ndim, params0)

    # -- posterior bank: snapshots from training, or a synthetic stand-in --
    def bank_steps():
        from repro.checkpoint import latest_bank_step
        import os, re
        from repro.checkpoint.checkpoint import BANK_PREFIX
        if not args.ckpt_dir or not os.path.isdir(args.ckpt_dir):
            return []
        out = []
        for fn in os.listdir(args.ckpt_dir):
            m = re.match(rf"{BANK_PREFIX}(\d+)\.npz", fn)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    steps = bank_steps()
    if steps:
        first = steps[0] if args.follow_snapshots else steps[-1]
        stacked = load_bank(args.ckpt_dir, step=first, like=params0)
        pending_steps = [s for s in steps if s > first]
    else:
        if args.ckpt_dir:
            raise SystemExit(f"no bank_*.npz snapshots in {args.ckpt_dir}; "
                             f"run launch.train with --bank-capacity")
        # synthetic posterior: jittered init standing in for an SGLD chain
        samples = [model.init(jax.random.fold_in(key, i))
                   for i in range(args.samples)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *samples)
        pending_steps = []
    lead = jax.tree.leaves(stacked)[0].ndim - jax.tree.leaves(base_ndims)[0]
    node_axis = 1 if lead == 2 else None    # (S, K, ...) trainer banks
    bank0 = stacked

    # -- engine + requests -------------------------------------------------
    if mode == "classify":
        from repro.data.radar import make_dataset
        ds = make_dataset(args.requests, hw=cfg.input_hw, seed=args.seed + 7)
        apply_fn = (lambda p, b: model.logits(p, b))
        eng = ClassifyEngine(apply_fn, scfg, input_shape=ds["x"].shape[1:],
                             stacked=stacked, node_axis=node_axis, mesh=mesh)
        reqs = [ServeRequest(x=ds["x"][i]) for i in range(args.requests)]
    else:
        if node_axis is not None:    # flatten (S, K, ...) -> (S*K, ...)
            stacked = jax.tree.map(
                lambda x: x.reshape((-1,) + x.shape[2:]), stacked)
        eng = DecodeEngine(model, scfg, stacked=stacked, mesh=mesh)
        reqs = [ServeRequest(prompt_token=1 + (i % max(cfg.vocab_size - 1, 1)),
                             seed=args.seed + i)
                for i in range(args.requests)]

    # warmup: one request through the full path, then freeze compile count
    warm = eng.run([reqs[0]])
    compiles0 = eng.compile_count()

    def maybe_swap():
        nonlocal pending_steps
        if args.poll_s > 0:
            new = [s for s in bank_steps()
                   if s not in pending_steps and s > (steps[-1] if steps
                                                     else -1)]
            pending_steps.extend(new)
        if pending_steps:
            s = pending_steps.pop(0)
            eng.install_bank(load_bank(args.ckpt_dir, step=s, like=params0))
            print(f"hot-swap: installed bank_{s:08d} "
                  f"(version {eng.bank_version}, in-flight "
                  f"{eng.pending()})")

    for r in reqs[1:]:
        eng.submit(r)
    t0 = time.perf_counter()
    resps = list(warm)
    last_poll = t0
    while eng.pending():
        resps.extend(eng.step())
        now = time.perf_counter()
        if pending_steps or (args.poll_s > 0
                             and now - last_poll >= args.poll_s):
            maybe_swap()
            last_poll = now
    dt = max(time.perf_counter() - t0, 1e-9)
    resps.sort(key=lambda r: r.request_id)

    for r in resps[:4]:
        extra = (f" tokens={r.tokens.tolist()}"
                 if r.tokens is not None else "")
        print(f"resp id={r.request_id} pred={int(np.argmax(r.probs))} "
              f"entropy={r.entropy:.3f} abstain={r.abstain} "
              f"bank_version={r.bank_version} "
              f"latency_ms={1e3 * r.latency_s:.2f}{extra}")
    st = eng.stats()
    served = len(resps)
    recompiles = eng.compile_count() - compiles0
    print(f"serve[{mode}]: arch={cfg.name} samples={eng.num_samples()} "
          f"slots={scfg.slots} requests={served}")
    print(f"serve: requests_per_s={(served - 1) / dt:.2f} "
          f"p50_ms={st['p50_ms']:.2f} p99_ms={st['p99_ms']:.2f} "
          f"abstain_rate={st['abstain_rate']:.3f} "
          f"entropy_mean={np.mean([r.entropy for r in resps]):.3f} "
          f"compiles={eng.compile_count()} recompiles={recompiles} "
          f"bank_version={eng.bank_version}")
    print(f"serve: slot_occupancy={st['slot_occupancy']:.3f} "
          f"queued_share={st['queued_share']:.3f} "
          f"queue_ms_mean={st['queue_ms_mean']:.2f}")
    if args.smoke:
        assert recompiles == 0, \
            f"{recompiles} recompiles after warmup (continuous batching " \
            f"must hold shapes fixed)"
        assert served == args.requests
        r = resps[0]
        assert r.probs.ndim == 1 and np.isfinite(r.entropy)
        assert isinstance(r.abstain, bool)
        print("SMOKE OK: zero recompiles after warmup; response carries "
              "probs/entropy/abstain/latency/bank_version")
    return ServeRun(resps, reqs, bank0, node_axis, recompiles)


if __name__ == "__main__":
    main()
