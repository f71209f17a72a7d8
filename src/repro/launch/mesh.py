"""Production mesh definitions.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis carries the federated nodes of CD-BFL in the cross-pod deployment
(DESIGN.md §2): CD-BFL compresses exactly the traffic that crosses it.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Auto axes: GSPMD propagates shardings and the activation hints of
    ``models/sharding_hints.py`` stay hints, not asserts."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_fed_mesh(num_shards: int = 0, fed_axis: str = "fed"):
    """1-D mesh whose single axis carries the federated node axis K.

    ``num_shards=0`` uses every visible device. This is the mesh the shard
    round engine (``train/engine.py: ShardRoundEngine``) and the
    GSPMD-auto path of ``launch/train.py --mesh N`` run on; on CPU, force
    devices first (``repro.launch.xla_flags.force_host_device_count``).
    """
    devices = jax.devices()
    n = num_shards or len(devices)
    if n > len(devices):
        hint = (f"on the CPU backend, call repro.launch.xla_flags."
                f"force_host_device_count({n}) before JAX initializes"
                if devices[0].platform == "cpu" else
                f"this host has {len(devices)} {devices[0].platform} "
                f"device(s): use --mesh {len(devices)} or fewer")
        raise ValueError(f"requested {n} shards but only {len(devices)} "
                         f"devices are visible; {hint}")
    return jax.make_mesh((n,), (fed_axis,))


def data_axes(mesh) -> tuple:
    """Axes that carry the batch dimension."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
