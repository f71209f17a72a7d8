"""Where JAX's persistent compilation cache lives, for every entry point.

Each entry point (``launch/train.py``, ``launch/serve.py``,
``launch/evaluate.py``, ``benchmarks/run.py``, ``chip_smoke.py``) calls
:func:`enable_compile_cache` first thing in its ``main`` — never at import,
so importing a module from a test leaves the process's cache alone.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing is
  changed here.
* Unset: the cache goes to ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``). The path is part of the cache key, so it is fixed to
  the checkout rather than to the working directory or a temp dir.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
