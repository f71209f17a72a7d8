"""Wire accounting: measured payload bytes vs the formula table, plus
pack/unpack throughput of the codec layer (DESIGN.md §2).

For each codec pipeline this reports, over the radar LeNet parameter tree
(the paper's model at CI scale):

* ``measured`` — :meth:`WirePayload.measured_bytes`, summed over the
  actual packed buffers (values + uint16 block-local indices + scales +
  rand-k keys);
* ``formula`` — the closed-form byte table kept as the cross-check;
* the measured/formula ratio (1.0 for sparse codecs up to index-width
  rounding; ~8/bits for the quantizers, whose sub-byte grids materialize
  byte-aligned);
* the paper's headline saving vs a dense fp32 exchange.

Throughput rows time encode/decode of the jnp pipeline and the Pallas
pack/unpack kernels. CAVEAT (same as
bench_kernels): Pallas runs interpret=True on CPU, so kernel wall time is
NOT TPU performance — rows exist to track relative regressions.

    PYTHONPATH=src python benchmarks/bench_wire.py [--tiny|--quick]
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timeit
from repro.config import get_arch
from repro.core.compression import parse_pipeline
from repro.kernels import ops
from repro.models import get_model

KEY = jax.random.PRNGKey(0)
RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results", "wire")

PIPELINES = [
    "topk", "block_topk", "randk", "qsgd", "sign",
    "block_topk|qsgd", "block_topk|sign", "randk|qsgd",
]


def _param_tree(tiny: bool):
    cfg = get_arch("lenet-radar").reduced
    model = get_model(cfg)
    tree = model.init(KEY)
    if tiny:
        tree = jax.tree.map(lambda x: x[..., :1] if x.ndim > 1 else x, tree)
    return tree


def _accounting_rows(tree, ratio: float, save: bool,
                     results_dir: str = None) -> List[str]:
    dense = 4 * sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    rows = []
    for spec in PIPELINES:
        pipe = parse_pipeline(spec, ratio=ratio, block_size=1024)
        payload = pipe.encode(tree, KEY)
        measured = payload.measured_bytes()
        formula = pipe.formula_bytes(tree)
        # round-trip sanity: the payload decodes to the dense masked tensor
        out = pipe.decode(payload)
        assert all(a.shape == b.shape for a, b in
                   zip(jax.tree.leaves(tree), jax.tree.leaves(out)))
        rec = {
            "pipeline": spec, "ratio": ratio,
            "measured_bytes": measured, "formula_bytes": formula,
            "measured_over_formula": measured / max(formula, 1),
            "dense_bytes": dense,
            "saving_pct": 100.0 * (1 - measured / dense),
            "delta": pipe.delta_for(tree),
        }
        if save:
            results_dir = results_dir or RESULTS_DIR
            os.makedirs(results_dir, exist_ok=True)
            fn = spec.replace("|", "_")
            with open(os.path.join(results_dir, f"{fn}.json"), "w") as f:
                json.dump(rec, f, indent=1)
        rows.append(
            f"wire_{spec.replace('|', '_')},0,"
            f"measured={measured};formula={formula};"
            f"m_over_f={rec['measured_over_formula']:.3f};"
            f"saving={rec['saving_pct']:.2f}%;delta={rec['delta']:.4g}")
    return rows


def _throughput_rows(n: int) -> List[str]:
    x = jax.random.normal(KEY, (n,))
    tree = {"w": x}
    rows = []
    # pipeline encode+decode (jnp path)
    pipe = parse_pipeline("block_topk", ratio=0.01, block_size=1024)
    enc = jax.jit(pipe.encode)
    payload = enc(tree, KEY)
    t = timeit(lambda: enc(tree, KEY), iters=3)
    rows.append(f"wire_encode_jnp,{t:.0f},n={n}")
    dec = jax.jit(pipe.decode)
    t = timeit(lambda: dec(payload), iters=3)
    rows.append(f"wire_decode_jnp,{t:.0f},n={n}")
    # Pallas pack/unpack kernels (interpret=True on CPU)
    t = timeit(lambda: ops.block_topk_pack(x, ratio=0.01, block_size=1024),
               iters=3)
    rows.append(f"wire_pack_pallas_interp,{t:.0f},n={n}")
    vals, idx = ops.block_topk_pack(x, ratio=0.01, block_size=1024)
    t = timeit(lambda: ops.block_topk_unpack(vals, idx, n, (n,),
                                             block_size=1024), iters=3)
    rows.append(f"wire_unpack_pallas_interp,{t:.0f},n={n}")
    return rows


def run(quick: bool = False, tiny: bool = False) -> List[str]:
    """Benchmark-suite entry point (CSV rows for benchmarks.run).

    ``--tiny`` saves its (machine-independent) accounting records under
    ``results/wire_tiny/`` — the byte half of the CI regression gate
    (``benchmarks/check_regression.py``) — keeping the full-tree records
    under ``results/wire/`` untouched.
    """
    tree = _param_tree(tiny)
    tiny_dir = os.path.join(os.path.dirname(__file__), "results",
                            "wire_tiny")
    rows = _accounting_rows(tree, ratio=0.01, save=True,
                            results_dir=tiny_dir if tiny else None)
    if tiny:
        rows += _throughput_rows(2 ** 14)
    else:
        rows += _throughput_rows(2 ** 18 if quick else 2 ** 21)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: trimmed tree + small leaves, ~seconds")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for row in run(quick=args.quick, tiny=args.tiny):
        print(row)


if __name__ == "__main__":
    main()
