"""Work counts for SmolLM-135M, from its shapes alone.

Operations count a multiply and an add as two. A token's forward pass costs
2 p (every weight once, the tied head included; the embedding lookup is no
product) and, in each layer, causal attention's two products over the
(S + 1) / 2 keys a query sees on average: 4 H hd (S + 1) / 2. A training
token costs three forward passes (the forward, and the two products of the
backward pass); the recomputation of the layers in the backward is not
counted.
"""
from __future__ import annotations

import math

from bench.reference.smollm import flat_shapes, num_params


def tokens_per_round(traffic) -> int:
    return (int(traffic["nodes"]) * int(traffic["local_steps"])
            * int(traffic["batch"]) * int(traffic["seq_len"]))


def forward_flops_per_token(cfg, seq_len: int) -> float:
    hd = cfg["d_model"] // cfg["num_heads"]
    attention = 4 * cfg["num_heads"] * hd * (seq_len + 1) / 2
    return 2 * num_params(cfg) + cfg["num_layers"] * attention


def train_flops_per_round(cfg, traffic) -> float:
    return (3 * forward_flops_per_token(cfg, int(traffic["seq_len"]))
            * tokens_per_round(traffic))


def xent_flops_per_round(cfg, traffic) -> float:
    """The tied head's product in the forward and its two in the backward:
    6 d V a token."""
    return 6 * cfg["d_model"] * cfg["vocab_size"] * tokens_per_round(traffic)


def leaf_sizes(cfg) -> list:
    return [math.prod(s) for _, s in flat_shapes(cfg)]


def params(cfg) -> int:
    return num_params(cfg)
