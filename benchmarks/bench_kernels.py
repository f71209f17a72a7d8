"""Kernel micro-benchmarks: Pallas (interpret) vs pure-jnp reference.

CAVEAT printed with results: interpret=True executes the kernel body via
the CPU interpreter, so *wall time here is NOT TPU performance* — the CSV
exists to track relative regressions and to validate call overhead. TPU
performance is assessed structurally in EXPERIMENTS.md §Roofline.

``--tiny`` writes the machine-independent gate records under
``results/kernels/`` for check_regression: live bitwise-parity bits
(pack/unpack round-trip vs the bisection reference, the QSGD carrier
kernel vs the codec stage under jit, fused delta-pack vs
pack-after-materialize) and the fused-update HBM traffic model — exact
integers, safe to hard-gate.

    PYTHONPATH=src python -m benchmarks.bench_kernels [--tiny|--quick]
"""
from __future__ import annotations

import argparse
import json
import os
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timeit
from repro.core.compression import QSGDCodec
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)
RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results", "kernels")


def _parity_record(n: int) -> dict:
    """Exact parity bits between the Pallas kernels and their references
    (all checks under jit — the kernels' bitwise contract; see
    tests/test_kernels.py on why eager differs in the last ulp)."""
    x = jax.random.normal(KEY, (n,))
    # pack -> unpack round-trips to the bisection reference's masked leaf
    vals, idx = ops.block_topk_pack(x, ratio=0.01, block_size=1024)
    back = ops.block_topk_unpack(vals, idx, n, (n,), block_size=1024)
    x2d, _ = ops._pad_to_2d(x, 1024, 8)
    dense = ref.block_topk_bisect_ref(x2d, 11).reshape(-1)[:n]
    pack_rt = int(np.array_equal(np.asarray(back), np.asarray(dense)))
    # qsgd carrier kernel vs the jitted codec stage, on the packed carrier
    got = jax.jit(lambda c, k: ops.qsgd_quantize_carrier(c, k, levels=16))(
        vals, KEY)
    want = jax.jit(lambda c, k: QSGDCodec(levels=16).encode(c, k)[:2])(
        vals, KEY)
    qsgd_match = int(np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
                     and np.array_equal(np.asarray(got[1]).reshape(1),
                                        np.asarray(want[1]["scale"])))
    # fused delta-pack vs pack of the materialized residual
    v = 0.1 * jax.random.normal(jax.random.fold_in(KEY, 1), (n,))
    fv, fi = ops.fused_delta_pack(x, v, ratio=0.01, block_size=1024)
    mv, mi = jax.jit(lambda t, vv: ops.block_topk_pack(
        t - vv, ratio=0.01, block_size=1024))(x, v)
    fused_match = int(np.array_equal(np.asarray(fv), np.asarray(mv))
                      and np.array_equal(np.asarray(fi), np.asarray(mi)))
    return {"n": n, "bitwise_pack_roundtrip": pack_rt,
            "bitwise_qsgd_vs_codec": qsgd_match,
            "bitwise_fused_delta_pack": fused_match}


def run(quick: bool = False, tiny: bool = False) -> List[str]:
    if tiny:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        rec = _parity_record(2 ** 14)
        # fused Eq. 9 update HBM model (f32 bytes of the 9n-vs-5n floats)
        traffic = {"unfused_bytes_per_elem": 36, "fused_bytes_per_elem": 20}
        with open(os.path.join(RESULTS_DIR, "parity.json"), "w") as f:
            json.dump(rec, f, indent=1)
        with open(os.path.join(RESULTS_DIR, "fused_update_traffic.json"),
                  "w") as f:
            json.dump(traffic, f, indent=1)
        return [
            f"kernel_parity,0,pack_rt={rec['bitwise_pack_roundtrip']};"
            f"qsgd={rec['bitwise_qsgd_vs_codec']};"
            f"fused_delta_pack={rec['bitwise_fused_delta_pack']};"
            f"n={rec['n']}",
            "kernel_fused_update_traffic_model,0,"
            "unfused_floats=9n;fused_floats=5n;cut=1.80x",
        ]
    rows = []
    n = 2 ** 18 if quick else 2 ** 21   # 2M params ~ the paper's LeNet
    x = jax.random.normal(KEY, (n,))

    # block top-k reference (the pack kernel's row is bench_wire's)
    x2d, _ = ops._pad_to_2d(x, 1024, 8)
    jref = jax.jit(lambda a: ref.block_topk_ref(a, k=11))
    t_ref = timeit(lambda: jref(x2d), iters=3)
    rows.append(f"kernel_block_topk_jnp_ref,{t_ref:.0f},n={n}")

    # fused update
    ks = jax.random.split(KEY, 4)
    th, vb, v, xi = [jax.random.normal(k, (n,)) for k in ks]
    t_pallas = timeit(lambda: ops.fused_update(th, vb, v, xi, zeta=0.03,
                                               noise_scale=0.014), iters=3)
    jref2 = jax.jit(lambda a, b, c, d: ref.fused_update_ref(a, b, c, d, 0.03, 0.014))
    t_ref = timeit(lambda: jref2(th, vb, v, xi), iters=3)
    rows.append(f"kernel_fused_update_pallas_interp,{t_pallas:.0f},n={n}")
    rows.append(f"kernel_fused_update_jnp_ref,{t_ref:.0f},n={n}")

    # fused compress-in-update (DESIGN.md §13)
    v = 0.1 * jax.random.normal(jax.random.fold_in(KEY, 1), (n,))
    t_fused = timeit(lambda: ops.fused_delta_pack(x, v, ratio=0.01,
                                                  block_size=1024), iters=3)
    rows.append(f"kernel_fused_delta_pack_interp,{t_fused:.0f},n={n}")
    vals, _ = ops.fused_delta_pack(x, v, ratio=0.01, block_size=1024)
    t_q = timeit(lambda: ops.qsgd_quantize_carrier(vals, KEY, levels=16),
                 iters=3)
    rows.append(f"kernel_qsgd_carrier_interp,{t_q:.0f},"
                f"carrier={vals.shape[0]}x{vals.shape[1]}")

    # derived: HBM traffic model for the fused kernel on TPU
    # unfused: 3 elementwise ops = (2+2+2) reads + 3 writes = 9n floats
    # fused: 4 reads + 1 write = 5n floats -> 1.8x traffic cut
    rows.append("kernel_fused_update_traffic_model,0,"
                "unfused_floats=9n;fused_floats=5n;cut=1.80x")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: parity gate records, ~seconds")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for row in run(quick=args.quick, tiny=args.tiny):
        print(row)


if __name__ == "__main__":
    main()
