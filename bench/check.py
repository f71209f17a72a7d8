"""The comparison that decides ``correct``: numbers, each with its limit.

Every number is a gap between what the timed path produced and what the
plain reference computes from the same seed, as a share of the reference.
A number whose limit is null in the cell's limits file is reported and not
compared (``PERF.md`` says why for each).
"""
from __future__ import annotations

import numpy as np


def rel_gap(got, want) -> float:
    """Largest ``|got - want| / |want|`` over paired scalars."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-30)))


def leaf_norm_gap(got: dict, want: dict, floor_share: float = 1e-3) -> float:
    """Worst leaf's gap between the program's norm and the reference's, over
    the larger of that leaf's reference norm and the median leaf's. Leaves
    whose reference norm is under ``floor_share`` of the median leaf's (a
    quantity nought to rounding) are left out."""
    names = sorted(want)
    ref = np.array([want[n] for n in names], np.float64)
    prog = np.array([got[n] for n in names], np.float64)
    med = float(np.median(ref))
    keep = ref >= floor_share * med
    if not np.any(keep):
        return 0.0
    return float(np.max(np.abs(prog[keep] - ref[keep])
                        / np.maximum(ref[keep], med)))


def leaf_norms(tree_flat: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in tree_flat.items()}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the compared table: every number with its limit; a
    number with no limit in the file is shown with ``None`` and not judged.
    A number that is not finite fails."""
    table, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        table[name] = {"value": value, "limit": limit}
        if limit is None:
            continue
        if not (np.isfinite(value) and value <= limit):
            ok = False
    if not any(limits.get(n) is not None for n in numbers):
        ok = False                  # a cell with nothing compared is not proven
    return ok, table
