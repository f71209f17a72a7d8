"""Work counts for LeNet, from its shapes alone.

Operations count a multiply and an add as two; a training sample costs three
forward passes (the forward, and the two products of the backward pass).
"""
from __future__ import annotations

from bench.reference.lenet import num_params, shapes


def forward_flops_per_sample(cfg) -> int:
    h, w = cfg["input_hw"]
    k = cfg["kernel"]
    total, c_in = 0, 1
    for c_out in (cfg["conv1_channels"], cfg["conv2_channels"]):
        h, w = h - k + 1, w - k + 1
        total += 2 * h * w * c_out * k * k * c_in
        h, w, c_in = h // 2, w // 2, c_out
    dims = [c_in * h * w, cfg["fc1"], cfg["fc2"], cfg["num_classes"]]
    total += sum(2 * a * b for a, b in zip(dims, dims[1:]))
    return total


def train_flops_per_round(cfg, traffic) -> int:
    samples = traffic["nodes"] * traffic["local_steps"] * traffic["batch"]
    return 3 * forward_flops_per_sample(cfg) * samples


def leaf_sizes(cfg) -> list:
    import math
    return [math.prod(s) for layer in shapes(cfg).values()
            for s in layer.values()]


def params(cfg) -> int:
    return num_params(cfg)
