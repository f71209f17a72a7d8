"""Readings that set a cell's limits: the program on many seeds, the control,
and planted faults, in one process on the chip.

    python3 bench/control.py --workload lenet-radar.train-k10-ring \
        --seeds 1,2,3 --control-seeds 4,5,6 --faults half_batch

* program: the timed path's first chunk (training) or a short window at
  the cell's own load (serving), compared with the reference;
* control: the reference itself computed in the next precision below the
  configuration's (``control_dtype``), put in the program's place and
  compared the same way;
* each fault of ``bench/faults.py``, planted in the program;
* for a serving cell, ``--sweep``: one window at each offered rate, to find
  the knee (the highest rate served without a growing backlog) that the
  traffic file's fixed rate is set from.

Prints one JSON line per reading. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from bench import common, faults, run  # noqa: E402

def control_dtype(cfg: dict) -> str:
    """The next precision below the configuration's products: float32 at
    ``highest`` -> bfloat16; float32 at default precision (one bfloat16 pass
    on a TPU) or bfloat16 -> float8 (e4m3)."""
    if cfg["dtype"] == "float32" and cfg.get("matmul_precision") == "highest":
        return "bfloat16"
    return "float8_e4m3fn"


def _emit(kind, seed, numbers, **extra):
    print(json.dumps({"kind": kind, "seed": seed, "numbers": numbers,
                      **extra}), flush=True)


def train_program(cell, seed):
    from bench import train
    cfg, tr = cell["config_data"], cell["traffic_data"]
    engine, state, key, bank = train.build(cfg, tr, seed)
    chunk = int(tr["chunk"])
    state, key, bank, losses, cons = engine.run(state, key, bank, chunk,
                                                t0=0, log_every=chunk)
    got = train._checked_outputs(bank, state, tr, losses, cons)
    del engine, state, key, bank
    return train.compare(cfg, tr, seed, got)


def train_control(cell, seed, dtype):
    import numpy as np
    from bench import generate, train
    from bench.reference.cdbfl import CDBFL
    cfg, tr = cell["config_data"], cell["traffic_data"]
    ref = common.reference_model(cfg)
    rounds = train.CHECK_ROUNDS if int(tr.get("bank_capacity", 0)) else \
        int(tr["chunk"])
    keep = ((1, train.CHECK_ROUNDS) if rounds == train.CHECK_ROUNDS
            else (rounds,))
    out = CDBFL(ref.nll_for(cfg), tr, dtype=dtype).run(
        ref.init_params(cfg, seed), train.make_data(cfg, tr, seed),
        generate.key(seed, generate.SALT_KEY), rounds, keep=keep)
    flat = train._flat
    got = {"loss": [o["loss"] for o in out[:train.CHECK_ROUNDS]],
           "consensus": [o["consensus"] for o in out[:train.CHECK_ROUNDS]],
           "params": {}}
    for r in keep:
        got["params"][r] = {k: np.asarray(v, np.float32)
                            for k, v in flat(out[r - 1]["params"]).items()}
    return train.compare(cfg, tr, seed, got)


def serve_program(cell, seed, seconds, rate=None):
    from bench import serve
    res = serve.run(cell, seed=seed, seconds=seconds, trace=False, rate=rate,
                    t_start=time.time())
    return {k: v["value"] for k, v in res["compared"].items()}, res


def serve_control(cell, seed, dtype):
    import numpy as np
    from bench import generate, serve
    cfg, tr = cell["config_data"], cell["traffic_data"]
    rng = np.random.default_rng(common.seed_bits(seed, generate.SALT_SAMPLE))
    frames = rng.integers(0, int(tr["frames"]), int(tr["checked"]))
    c = serve.reference_answers(cfg, tr, seed, frames, dtype)
    got = {"frame": frames, "probs": c["probs"], "entropy": c["entropy"],
           "abstain": c["entropy"] > float(tr["entropy_threshold"])}
    return serve.compare(cfg, tr, seed, got)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="serving: length of each short window")
    ap.add_argument("--sweep", default="",
                    help="serving: offered rates (req/s) for the knee sweep")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.check_chips(int(cell["chips"]))
    common.enable_compile_cache()
    cell["limits"] = {}
    kind = cell["traffic_data"]["kind"]
    dtype = control_dtype(cell["config_data"])
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    for i, r in enumerate(float(r) for r in args.sweep.split(",") if r):
        nums, res = serve_program(cell, (seeds or [0])[0] + i, args.seconds,
                                  rate=r)
        _emit("sweep", (seeds or [0])[0] + i, nums, rate=r,
              counts=res["counts"], metrics=res["metrics"])
    for s in seeds:
        if kind == "train":
            _emit("program", s, train_program(cell, s))
        else:
            nums, res = serve_program(cell, s, args.seconds)
            _emit("program", s, nums, counts=res["counts"],
                  metrics=res["metrics"])
    for s in cseeds:
        _emit(f"control:{dtype}", s, train_control(cell, s, dtype)
              if kind == "train" else serve_control(cell, s, dtype))
    for f in [f for f in args.faults.split(",") if f]:
        for s in cseeds:
            with faults.plant(f):
                if kind == "train":
                    _emit(f"fault:{f}", s, train_program(cell, s))
                else:
                    _emit(f"fault:{f}", s, serve_program(cell, s,
                                                         args.seconds)[0])


if __name__ == "__main__":
    main()
