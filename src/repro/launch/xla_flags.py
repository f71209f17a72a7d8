"""Forcing the CPU backend's device count for multi-device runs on a host.

The CPU backend fixes its device count the moment JAX initializes, so the
count must be set before that — and never by a mere import: importing a
dry-run helper from a test (or from the shard engine) must not reconfigure
the process's backend. Entry points call :func:`force_host_device_count`
under their ``__main__`` guard instead. The setting is JAX's public
``jax_num_cpu_devices`` option; it touches only the CPU backend, so on a
TPU host it changes nothing.

This module must stay importable without importing JAX.
"""
from __future__ import annotations

import warnings


def force_host_device_count(n: int) -> bool:
    """Give the CPU backend ``n`` devices; True when the count was set.

    Once a backend exists the count cannot change anymore: JAX refuses
    the update, and this returns False, warning when the visible device
    count differs from ``n``.
    """
    import jax
    try:
        jax.config.update("jax_num_cpu_devices", n)
        return True
    except RuntimeError:
        have = len(jax.devices())
        if have != n:
            warnings.warn(
                f"JAX backend already initialized with {have} device(s); "
                f"cannot force {n} host devices now. Call "
                f"force_host_device_count before the first JAX operation.",
                stacklevel=2)
        return False
