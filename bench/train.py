"""A training cell: CD-BFL rounds through the program's round engine.

Set-up builds the engine exactly as ``repro.launch.train`` does
(``make_round_fn("cdbfl", ...)``, ``make_compressor(FedConfig(...))``,
``DeviceSampleBank`` where the traffic has a bank, ``make_engine``) on
weights, data and keys made from the seed, and drives it through two
chunks: the first is the one checked against the reference, the second
makes sure the window finds every program compiled. The window then calls
``engine.run`` one chunk at a time until ``--seconds`` have passed; every
chunk ends in the engine's own metrics sync, so all rounds and all gaps
count. ``rounds_per_s`` is the rounds completed over the window's length.
The span ``dispatch_chunk`` covers one ``engine.run``: its dispatch and the
metrics transfer that ends it, which the engine makes inside.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import check, common, generate

CHECK_ROUNDS = 3


def _flat(tree) -> dict:
    import jax
    return {jax.tree_util.keystr(p): v
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def program_model_config(cfg: dict):
    """The program's ModelConfig for ``cfg``: the registered architecture
    with every key the file gives that the ModelConfig has."""
    import dataclasses

    from repro.config import get_arch
    base = get_arch(cfg["arch"]).config
    fields = {f.name for f in dataclasses.fields(base)}
    over = {k: (tuple(v) if isinstance(v, list) else v)
            for k, v in cfg.items() if k in fields and k != "name"}
    return base.replace(**over)


def make_data(cfg: dict, traffic: dict, seed: int) -> dict:
    """Per-node training pools on the device: ``field -> (K, N, ...)``."""
    import jax.numpy as jnp
    data = common.reference_model(cfg).make_pool(cfg, traffic, seed)
    data["size"] = jnp.full((int(traffic["nodes"]),), int(traffic["pool"]),
                            jnp.int32)
    return data


def build(cfg: dict, traffic: dict, seed: int):
    """The engine, its initial state, trainer key and bank, as
    ``repro.launch.train.main`` builds them."""
    import jax
    from repro.config import FedConfig, TopologyConfig
    from repro.core import (build_topology, init_fed_state, make_compressor,
                            make_round_fn)
    from repro.data.partition import DeviceShards
    from repro.models import get_model
    from repro.train.engine import make_engine

    model = get_model(program_model_config(cfg))
    ref = common.reference_model(cfg)
    topo_cfg = TopologyConfig(graph=traffic["graph"])
    fed = FedConfig(
        num_nodes=int(traffic["nodes"]), local_steps=int(traffic["local_steps"]),
        eta=float(traffic["eta"]), zeta=float(traffic["zeta"]),
        temperature=float(traffic.get("temperature", 1.0)),
        topology=traffic["graph"], topology_cfg=topo_cfg,
        compressor=traffic["codec"], compress_ratio=float(traffic["ratio"]),
        block_size=int(traffic["block"]),
        fused_compress=bool(traffic["fused"]), algorithm="cdbfl")
    topo = build_topology(topo_cfg, fed.num_nodes)
    comp = make_compressor(fed)
    round_fn = make_round_fn("cdbfl", model.loss, fed, topo.omega, comp,
                             data_scale=1.0)
    params0 = ref.init_params(cfg, seed)
    state = init_fed_state(params0, fed,
                           key=generate.key(seed, generate.SALT_STATE))
    del params0
    data = make_data(cfg, traffic, seed)
    sizes = data.pop("size")
    shards = DeviceShards(data=data, sizes=sizes,
                          example_field=ref.EXAMPLE_FIELD)
    bank_cfg = bank_state = None
    if int(traffic.get("bank_capacity", 0)) > 0:
        from repro.core.posterior import DeviceSampleBank
        bank_cfg = DeviceSampleBank(burn_in=int(traffic["burn_in"]),
                                    capacity=int(traffic["bank_capacity"]),
                                    thin=int(traffic["thin"]))
        bank_state = bank_cfg.init(state.params)
    engine = make_engine("scan", round_fn, shards, fed.local_steps,
                         int(traffic["batch"]), bank=bank_cfg,
                         chunk=int(traffic["chunk"]))
    key = generate.key(seed, generate.SALT_KEY)
    jax.block_until_ready((state, bank_state, shards.data))
    return engine, state, key, bank_state


def _checked_outputs(bank_state, state, traffic, losses, cons) -> dict:
    """What the first chunk produced, copied to the host before the window
    takes the state: per-round loss and consensus, and the params after
    rounds 1 and 3 from the bank (after the whole chunk where there is no
    bank)."""
    out = {"loss": list(losses[:CHECK_ROUNDS]),
           "consensus": list(cons[:CHECK_ROUNDS]), "params": {}}
    if bank_state is not None:
        for r in (1, CHECK_ROUNDS):
            out["params"][r] = {k: np.asarray(v[r - 1]) for k, v in
                                _flat(bank_state.slots).items()}
    else:
        out["params"][int(traffic["chunk"])] = {
            k: np.asarray(v) for k, v in _flat(state.params).items()}
    return out


def compare(cfg, traffic, seed, got: dict, dtype=None) -> dict:
    """The numbers ``correct`` is decided by: the reference (or, for the
    control, the reference in ``dtype``) follows the checked rounds from
    the same seed."""
    import jax.numpy as jnp

    from bench.reference.cdbfl import CDBFL
    ref_model = common.reference_model(cfg)
    rounds = max(max(got["params"]), len(got["loss"]))
    theta0 = ref_model.init_params(cfg, seed)
    data = make_data(cfg, traffic, seed)
    key = generate.key(seed, generate.SALT_KEY)
    want = CDBFL(ref_model.nll_for(cfg), traffic,
                 dtype=dtype or jnp.float32).run(theta0, data, key, rounds,
                                                 keep=got["params"])
    t0 = {k: np.asarray(v, np.float64) for k, v in _flat(theta0).items()}
    n = len(got["loss"])
    numbers = {
        "loss": check.rel_gap(got["loss"], [w["loss"] for w in want[:n]]),
        "consensus": check.rel_gap(got["consensus"],
                                   [w["consensus"] for w in want[:n]]),
    }
    for r, params in sorted(got["params"].items()):
        noise = _flat(want[r - 1]["noise"])
        wparams = _flat(want[r - 1]["params"])

        def moved(p):
            return {k: np.asarray(p[k], np.float64) - t0[k][None]
                    - np.asarray(noise[k], np.float64) for k in t0}
        name = "update1" if r == 1 else f"change{r}"
        numbers[name] = check.leaf_norm_gap(
            check.leaf_norms(moved(params)), check.leaf_norms(moved(wparams)))
    return numbers


def run(cell: dict, seed: int, seconds: float, trace: bool, rate,
        t_start: float) -> dict:
    from bench import readout
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    chips = int(cell["chips"])
    compiles = common.Compiles()
    engine, state, key, bank = build(cfg, traffic, seed)
    chunk = int(traffic["chunk"])
    done = 0
    state, key, bank, losses, cons = engine.run(state, key, bank, chunk,
                                                t0=done, log_every=chunk)
    done += chunk
    got = _checked_outputs(bank, state, traffic, losses, cons)
    state, key, bank, losses, cons = engine.run(state, key, bank, chunk,
                                                t0=done, log_every=chunk)
    done += chunk
    nonfinite = int(not np.all(np.isfinite(got["loss"])))

    tracer = readout.Tracer(trace)
    compiles.window_open = True
    setup_s = time.time() - t_start
    rounds = 0
    chunk_s = []
    with tracer:
        t0 = t1 = time.perf_counter()
        while True:
            with common.span("dispatch_chunk", trace):
                state, key, bank, losses, cons = engine.run(
                    state, key, bank, chunk, t0=done, log_every=chunk)
            nonfinite += int(not np.all(np.isfinite(losses)))
            done += chunk
            rounds += chunk
            chunk_s.append(time.perf_counter() - t1)
            t1 += chunk_s[-1]
            if t1 - t0 >= seconds:
                break
    compiles.window_open = False
    window_s = t1 - t0
    device = common.device_info(chips)
    del state, key, bank, engine
    gc.collect()

    numbers = compare(cfg, traffic, seed, got)
    correct, table = check.judge(numbers, cell["limits"])
    correct = correct and nonfinite == 0 and compiles.in_window == 0
    counts = {"rounds": rounds, "window_s": window_s, "chips": chips,
              "compiles_in_window": compiles.in_window,
              "compile_s": compiles.seconds, "cache_hits": compiles.hits,
              "chunks": rounds // chunk,
              "chunk_ms_median": 1e3 * float(np.median(chunk_s)),
              "chunk_ms_max": 1e3 * max(chunk_s)}
    e2e = {"rounds_per_s": rounds / window_s, "setup_s": setup_s}
    return readout.result(cell, trace, tracer, e2e, counts, device,
                          correct=correct, attempted=rounds,
                          failed=nonfinite * chunk, compared=table)
