"""Scenario-matrix evaluation driver (DESIGN.md §10).

Produces the scenario × algorithm × pipeline calibration matrix —
accuracy / ECE / NLL / Brier / overconfidence gap per cell — through the
fused :class:`~repro.eval.engine.ScanEvalEngine`, either from fresh
reduced-scale training runs or from a checkpoint.

    # 6-family × 3-severity matrix over cdbfl vs cffl, markdown to stdout
    PYTHONPATH=src python -m repro.launch.evaluate --quick

    # full registry, every severity, with ASCII reliability diagrams
    PYTHONPATH=src python -m repro.launch.evaluate --scenarios all \
        --severities 0.25,0.5,1.0 --diagrams --out matrix.md

    # score a checkpoint (point estimate) across the registry
    PYTHONPATH=src python -m repro.launch.evaluate --ckpt ckpts/ --scenarios all
"""
from __future__ import annotations

import argparse
import json
import os

from repro.launch.compile_cache import enable_compile_cache


DEFAULT_SCENARIOS = ("clean", "gain_drift", "clutter_ramp", "doa_miscal",
                     "snr_degradation", "room_geometry", "node_hetero")


def _parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="lenet-radar")
    ap.add_argument("--algorithms", default="cdbfl,cffl",
                    help="comma list from {cdbfl,dsgld,cffl}")
    ap.add_argument("--pipelines", default="",
                    help="comma list of codec DSL pipelines ('' = the "
                         "configured --compressor)")
    ap.add_argument("--compressor", default="block_topk")
    ap.add_argument("--ratio", type=float, default=0.01)
    ap.add_argument("--scenarios", default=",".join(DEFAULT_SCENARIOS),
                    help="comma list of shift families, or 'all'")
    ap.add_argument("--severities", default="0.5,1.0",
                    help="comma list of severity scalars in [0,1]")
    ap.add_argument("--nodes", type=int, default=5)
    ap.add_argument("--per-node", type=int, default=24)
    ap.add_argument("--rounds", type=int, default=150)
    ap.add_argument("--eval-examples", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir: score its params (point "
                         "estimate) instead of training")
    ap.add_argument("--quick", action="store_true",
                    help="60-round training runs (CI/laptop scale)")
    ap.add_argument("--diagrams", action="store_true",
                    help="print ASCII reliability diagrams per cell")
    ap.add_argument("--out", default=None, help="write markdown report here")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the raw cell rows as JSON here")
    return ap.parse_args()


def main():
    args = _parse_args()
    enable_compile_cache()
    from repro.core.calibration import render_reliability
    from repro.data.scenarios import list_scenarios
    from repro.eval.matrix import (MatrixSpec, evaluate_params_matrix,
                                   matrix_markdown, run_matrix)

    names = (list_scenarios() if args.scenarios == "all"
             else [s for s in args.scenarios.split(",") if s])
    sevs = [float(s) for s in args.severities.split(",") if s]
    # clean is severity-independent: evaluate it once
    cells = [(n, s) for n in names for s in
             (sevs if n != "clean" else sevs[:1])]

    if args.ckpt:
        from repro.checkpoint import load_checkpoint_tree
        params = load_checkpoint_tree(args.ckpt)
        out = evaluate_params_matrix(params, args.arch, cells,
                                     eval_examples=args.eval_examples,
                                     seed=args.seed)
    else:
        spec = MatrixSpec(
            algorithms=tuple(a for a in args.algorithms.split(",") if a),
            pipelines=tuple(args.pipelines.split(",")),
            cells=tuple(cells),
            nodes=args.nodes, per_node=args.per_node,
            rounds=60 if args.quick else args.rounds,
            compressor=args.compressor, compress_ratio=args.ratio,
            eval_examples=args.eval_examples, seed=args.seed,
            arch=args.arch,
        )
        out = run_matrix(spec)

    md = matrix_markdown(out)
    print()
    print(md)
    if args.diagrams:
        for c in out:
            print()
            print(render_reliability(
                c.report.bins,
                f"{c.algorithm}|{c.pipeline or '-'} "
                f"{c.scenario}@{c.severity:g}"))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write("# Scenario-matrix calibration report\n\n" + md + "\n")
        print(f"\nwrote {args.out}")
    if args.json_out:
        os.makedirs(os.path.dirname(args.json_out) or ".", exist_ok=True)
        with open(args.json_out, "w") as f:
            json.dump([c.row() for c in out], f, indent=1)
        print(f"wrote {args.json_out}")


if __name__ == "__main__":
    main()
