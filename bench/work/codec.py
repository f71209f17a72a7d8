"""Ideal HBM bytes of one round's encode and decode, per node, from shapes.

Block top-k over ``block``-entry blocks keeps ``ceil(ratio * block)`` values
(float32) and their block-local indices (uint16) per block. The work, not a
kernel, is counted: read theta and v (8 bytes an entry), write the wire,
read the wire back, write the dense delta (4 bytes an entry).
"""
from __future__ import annotations

import math


def wire_bytes(leaf_sizes, ratio: float, block: int) -> int:
    keep = max(1, math.ceil(ratio * block))
    return sum(max(1, -(-n // block)) * keep * (4 + 2) for n in leaf_sizes)


def ideal_bytes_per_node(leaf_sizes, ratio: float, block: int) -> int:
    p = sum(leaf_sizes)
    return 8 * p + 2 * wire_bytes(leaf_sizes, ratio, block) + 4 * p
