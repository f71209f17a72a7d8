"""A serving cell: BMA classify requests through ``ClassifyEngine.step``.

Set-up makes a bank of S posterior samples per node (``(S, K, ...)``, the
layout a trainer's bank has) and a pool of radar frames from the seed,
builds ``ClassifyEngine`` as ``repro.launch.serve`` does, and warms its two
programs (the slot write and the BMA predict) with the window's own shapes.

The window is an open loop in one thread: requests fall due on a Poisson
schedule at the traffic's fixed rate; each pass of the loop submits every
request that is due, then runs one ``step`` if anything is pending, or
sleeps until the next one falls due. Each request is timed from when it
was due, so a late submit counts. After the window the loop drains what is
pending (for at most ``DRAIN_S``); a request still unanswered then has
failed. ``serve_p95_ms`` is the 95th percentile of completion minus due
time over every request due in the window; ``serve_rps`` the responses
completed inside the window over its length.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import check, common, generate

DRAIN_S = 60.0


def build(cfg: dict, traffic: dict, seed: int):
    import jax
    from repro.config import ServeConfig
    from repro.models import get_model
    from repro.serve import ClassifyEngine

    from bench.train import program_model_config
    ref = common.reference_model(cfg)
    model = get_model(program_model_config(cfg))
    bank = ref.make_bank(cfg, seed, int(traffic["samples"]),
                         int(traffic["nodes"]), float(traffic["spread"]),
                         float(traffic["head_gain"]))
    frames = np.asarray(ref.make_frames(cfg, seed, int(traffic["frames"])))
    scfg = ServeConfig(slots=int(traffic["slots"]),
                       entropy_threshold=float(traffic["entropy_threshold"]))
    engine = ClassifyEngine(lambda p, b: model.logits(p, b), scfg,
                            input_shape=frames.shape[1:], stacked=bank,
                            node_axis=1)
    jax.block_until_ready(bank)
    return engine, frames


def warm(engine, frames) -> None:
    """Both programs, at the window's shapes: a full and a partial table."""
    from repro.serve import ServeRequest
    slots = engine.cfg.slots
    for n in (slots, 1):
        for i in range(n):
            engine.submit(ServeRequest(x=frames[i]))
        engine.drain()


def reference_answers(cfg, traffic, seed, frame_ids, dtype=None) -> dict:
    """BMA probabilities and entropy of the plain reference (or, for the
    control, the reference in ``dtype``) for the given frames, over the
    whole bank, made anew from the seed."""
    import jax.numpy as jnp
    ref = common.reference_model(cfg)
    frames = ref.make_frames(cfg, seed, int(traffic["frames"]))
    x = frames[np.asarray(frame_ids)]
    probs = ref.bma(cfg, seed, int(traffic["samples"]), int(traffic["nodes"]),
                    float(traffic["spread"]), float(traffic["head_gain"]), x,
                    dtype or jnp.float32)
    p = np.asarray(probs, np.float64)
    ent = -np.sum(p * np.log(np.maximum(p, 1e-12)), axis=-1)
    return {"probs": p, "entropy": ent}


def compare(cfg, traffic, seed, got: dict, dtype=None) -> dict:
    """Widest probability and entropy gaps over the sampled requests, and
    the abstain flags that disagree with the reference's entropy outside a
    band of the entropy limit around the threshold."""
    want = reference_answers(cfg, traffic, seed, got["frame"], dtype)
    thr = float(traffic["entropy_threshold"])
    band = float(traffic.get("abstain_band", 0.0))
    flags = want["entropy"] > thr
    clear = np.abs(want["entropy"] - thr) > band
    return {
        "probs": float(np.max(np.abs(np.asarray(got["probs"], np.float64)
                                     - want["probs"]))),
        "entropy": float(np.max(np.abs(np.asarray(got["entropy"])
                                       - want["entropy"]))),
        "abstain": float(np.sum((np.asarray(got["abstain"]) != flags)
                                & clear)),
    }


def serve_window(engine, frames, due, frame_ids, seconds, trace):
    """The open loop. Returns per-request due, submit and finish times (s
    from the window's start; NaN finish = never answered), the step times,
    and the responses by request index."""
    from repro.serve import ServeRequest
    n = len(due)
    sub = np.full(n, np.nan)
    fin = np.full(n, np.nan)
    rid_of = {}
    answers = {}
    steps = []
    i = 0
    t0 = time.perf_counter()
    deadline = seconds + DRAIN_S
    while True:
        now = time.perf_counter() - t0
        if i < n and due[i] <= now:
            with common.span("generate", trace):
                while i < n and due[i] <= now:
                    rid_of[engine.submit(ServeRequest(x=frames[frame_ids[i]]))] = i
                    sub[i] = time.perf_counter() - t0
                    i += 1
        if engine.pending():
            with common.span("step", trace):
                ts = time.perf_counter()
                out = engine.step()
                te = time.perf_counter()
            steps.append(te - ts)
            for r in out:
                j = rid_of.pop(r.request_id)
                fin[j] = te - t0
                answers[j] = r
        elif i < n:
            with common.span("wait", trace):
                time.sleep(max(0.0, due[i] - (time.perf_counter() - t0)))
        else:
            break
        if time.perf_counter() - t0 > deadline:
            break
    return sub, fin, steps, answers


def run(cell: dict, seed: int, seconds: float, trace: bool, rate,
        t_start: float) -> dict:
    from bench import readout
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    chips = int(cell["chips"])
    rate = float(traffic["rate"] if rate is None else rate)
    compiles = common.Compiles()
    engine, frames = build(cfg, traffic, seed)
    warm(engine, frames)
    due, frame_ids = generate.poisson_arrivals(seed, rate, seconds,
                                               len(frames))
    tracer = readout.Tracer(trace)
    compiles.window_open = True
    setup_s = time.time() - t_start
    with tracer:
        sub, fin, steps, answers = serve_window(engine, frames, due,
                                                frame_ids, seconds, trace)
    compiles.window_open = False
    device = common.device_info(chips)
    del engine
    gc.collect()

    n = len(due)
    answered = np.isfinite(fin)
    lat = np.where(answered, fin, seconds + DRAIN_S) - due
    served_in_window = int(np.sum(fin <= seconds))
    rng = np.random.default_rng(common.seed_bits(seed, generate.SALT_SAMPLE))
    done_ids = np.flatnonzero(answered)
    pick = np.sort(rng.choice(done_ids, min(int(traffic["checked"]),
                                            len(done_ids)), replace=False))
    got = {"frame": frame_ids[pick],
           "probs": np.stack([answers[j].probs for j in pick]),
           "entropy": np.array([answers[j].entropy for j in pick]),
           "abstain": np.array([answers[j].abstain for j in pick])}
    numbers = compare(cfg, traffic, seed, got)
    correct, table = check.judge(numbers, cell["limits"])
    failed = int(n - answered.sum())
    correct = correct and compiles.in_window == 0
    ent = np.array([a.entropy for a in answers.values()])
    counts = {"requests": n, "served_in_window": served_in_window,
              "window_s": seconds, "chips": chips, "steps": len(steps),
              "rate": rate, "compiles_in_window": compiles.in_window,
              "compile_s": compiles.seconds, "cache_hits": compiles.hits,
              "step_ms_median": 1e3 * float(np.median(steps)),
              "step_ms_max": 1e3 * float(np.max(steps)),
              "gen_lag_ms_p95": 1e3 * float(np.percentile(sub - due, 95)),
              "latency_ms_p50": 1e3 * float(np.percentile(lat, 50)),
              "samples": int(traffic["samples"]) * int(traffic["nodes"]),
              "abstain_share": float(np.mean(ent > float(
                  traffic["entropy_threshold"]))) if len(ent) else 0.0,
              "entropy_quartiles": [float(q) for q in np.percentile(
                  ent, [25, 50, 75])] if len(ent) else []}
    e2e = {"serve_p95_ms": 1e3 * float(np.percentile(lat, 95)),
           "serve_rps": served_in_window / seconds, "setup_s": setup_s}
    return readout.result(cell, trace, tracer, e2e, counts, device,
                          correct=correct, attempted=n, failed=failed,
                          compared=table)
