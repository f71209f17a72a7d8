"""Activation sharding hints (safe no-ops without a mesh).

GSPMD loses the batch sharding of q/k/v when they are restacked as scan
inputs for the chunked attention/recurrence paths (observed in the qwen
train_4k dry-run: attention dots executed with the FULL global batch per
device — a 16× compute waste). ``hint()`` re-anchors the intended sharding
with ``with_sharding_constraint``; outside a ``jax.set_mesh`` context (unit
tests, CPU examples) it is an identity.

Axis names are filtered against the active mesh, and dims that don't divide
fall back to replicated, so the same model code works on 1 CPU device, the
16×16 pod and the 2×16×16 multi-pod mesh.
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

AxisSpec = Union[None, str, Tuple[str, ...]]

BATCH_AXES: Tuple[str, ...] = ("pod", "data")
MODEL_AXIS = "model"

# axes reserved for the federated-node dim (set while tracing a fed step so
# batch hints don't fight the node sharding — observed +64% collectives on
# the deepseek fed step otherwise, EXPERIMENTS.md §Perf iter 3a)
_RESERVED: Tuple[str, ...] = ()


class reserve_axes:
    """Context manager: exclude mesh axes from hints during tracing."""

    def __init__(self, *axes: str):
        self.axes = tuple(axes)

    def __enter__(self):
        global _RESERVED
        self._prev = _RESERVED
        _RESERVED = self._prev + self.axes
        return self

    def __exit__(self, *exc):
        global _RESERVED
        _RESERVED = self._prev
        return False


def _current_mesh():
    """The mesh installed by ``jax.set_mesh``, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def hint(x, *spec: AxisSpec):
    """Constrain ``x`` to PartitionSpec(*spec) against the active mesh.

    Unknown axes are dropped; non-dividing dims are replicated; no mesh →
    identity. ``spec`` shorter than ``x.ndim`` is right-padded with None.
    """
    m = _current_mesh()
    if m is None:
        return x
    names = set(m.axis_names) - set(_RESERVED)
    clean = []
    for dim, s in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
        if s is None:
            clean.append(None)
            continue
        axes = tuple(a for a in ((s,) if isinstance(s, str) else s)
                     if a in names)
        if not axes:
            clean.append(None)
            continue
        total = int(np.prod([m.shape[a] for a in axes]))
        clean.append((axes if len(axes) > 1 else axes[0])
                     if (dim % total == 0 and dim >= total) else None)
    return jax.lax.with_sharding_constraint(x, P(*clean))


def hint_batch(x):
    """Shard dim 0 (batch) over the data axes, rest replicated."""
    return hint(x, BATCH_AXES)


def hint_bshd(x):
    """(B, S, H, hd): batch over data axes, heads over model if divisible."""
    return hint(x, BATCH_AXES, None, MODEL_AXIS, None)
