"""Run one benchmark cell on the chips of this machine and print its result.

    python3 bench/run.py --workload lenet-radar.train-k10-ring --seed 7 \
        --seconds 20 --trace 0

The cell is looked up by name in ``BENCHMARK.json``; its configuration is
``bench/configs/<config>.json``, its traffic ``bench/traffic/<traffic>.json``,
its correctness limits ``bench/limits/<cell>.json`` and each per-layer
metric a reader ``bench/metrics/<metric>.py``. Adding a cell adds files and
a ``workloads`` entry; nothing here changes.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the metrics are
its per-layer metrics, read from the trace and the run's counts. Every run
checks what the timed path produced against the plain reference under
``bench/reference/`` and prints each compared number beside its limit, as
the last lines of standard error and as the last key of the result.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``). Without a TPU, or with fewer chips than the cell asks
for, the run exits with 2 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from bench import common  # noqa: E402

T_START = common.process_start()


def _args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str) -> dict:
    """The cell's entry in BENCHMARK.json with its configuration, traffic,
    limits and metric lists resolved by name."""
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{sorted(cells)}")
    cell = dict(cells[name])
    cell["config_data"] = common.find("configs", cell["config"])
    cell["traffic_data"] = common.find("traffic", cell["traffic"])
    limits = common.BENCH / "limits" / f"{name}.json"
    cell["limits"] = common.load_json(limits) if limits.is_file() else {}

    def mine(m):
        return "workloads" not in m or name in m["workloads"]
    cell["end_to_end"] = [m for m in spec["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in spec["per_layer"] if mine(m)]
    return cell


def check_chips(chips: int) -> None:
    """Exit 2, printing no result, unless JAX sees enough TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"no result: this cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(2)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start=None) -> dict:
    """Everything after the look for a chip: set-up, window, check."""
    common.enable_compile_cache()
    kind = cell["traffic_data"]["kind"]
    if kind == "train":
        from bench import train as runner
    elif kind == "serve":
        from bench import serve as runner
    else:
        raise SystemExit(f"unknown traffic kind {kind!r}")
    t_start = T_START if t_start is None else t_start
    return runner.run(cell, seed=seed, seconds=seconds, trace=trace,
                      rate=None, t_start=t_start)


def main(argv=None) -> None:
    args = _args(argv)
    cell = load_cell(args.workload)
    check_chips(int(cell["chips"]))
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    compared = result["compared"]
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
