"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run            # full
    PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized
    PYTHONPATH=src python -m benchmarks.run --only fig3
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="substring filter: fig3|fig4|comm|kernel|roofline")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_ablation, bench_comm_overhead,
                            bench_drift, bench_eval_engine,
                            bench_fig3_l_sweep, bench_fig4_reliability,
                            bench_fused_compress, bench_kernels,
                            bench_round_engine, bench_serve,
                            bench_shard_engine, bench_topology_sweep,
                            bench_transport, bench_wire, roofline)
    suites = {
        "fig3_l_sweep": bench_fig3_l_sweep.run,
        "fig4_reliability": bench_fig4_reliability.run,
        "comm_overhead": bench_comm_overhead.run,
        "topology_sweep": bench_topology_sweep.run,
        "round_engine": bench_round_engine.run,
        "shard_engine": bench_shard_engine.run,
        "eval_engine": bench_eval_engine.run,
        "wire": bench_wire.run,
        "transport": bench_transport.run,
        "kernels": bench_kernels.run,
        "fused_compress": bench_fused_compress.run,
        "serve": bench_serve.run,
        "drift": bench_drift.run,
        "roofline": roofline.run,
    }
    # beyond-paper sweeps, opt-in (heavier): --only ablation
    if args.only and "ablation" in args.only:
        suites = {"ablation": bench_ablation.run}

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites.items():
        if args.only and args.only not in name:
            continue
        try:
            for row in fn(quick=args.quick):
                print(row)
        except Exception as e:
            failures += 1
            traceback.print_exc()
            print(f"{name}_FAILED,0,{type(e).__name__}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
