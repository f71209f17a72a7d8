"""Paper Table (implied, §V headline): communication overhead per method.

Bytes on the wire per device per round, for the paper's p=2.7M LeNet and
for the assigned production archs — showing the 99% claim and how it scales
to the multi-pod deployment where CD-BFL compresses inter-pod traffic.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import get_arch, list_archs
from repro.core.compression import parse_pipeline
from repro.models import get_model


def _tree_specs(cfg):
    model = get_model(cfg)
    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))


def run(quick: bool = False) -> List[str]:
    rows = []
    compressors = {
        "dense_fp32": parse_pipeline("identity"),
        "topk_1pct": parse_pipeline("topk", ratio=0.01),
        "block_topk_1pct": parse_pipeline("block_topk", ratio=0.01),
        "qsgd_4bit": parse_pipeline("qsgd", qsgd_levels=16),
        "sign_1bit": parse_pipeline("sign"),
    }

    # paper model at full scale (2.7M params, real 256x63 maps)
    archs = ["lenet-radar"] if quick else [
        "lenet-radar", "smollm-135m", "recurrentgemma-9b", "grok-1-314b"]
    for arch in archs:
        cfg = get_arch(arch).config
        specs = _tree_specs(cfg)
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(specs))
        dense = compressors["dense_fp32"].wire_bytes(specs)
        for cname, comp in compressors.items():
            b = comp.wire_bytes(specs)
            rows.append(
                f"comm_{arch}_{cname},0,"
                f"params={n};bytes_per_node_round={b:.4g};"
                f"saving_pct={100*(1-b/dense):.2f}")
    return rows
