"""What every cell shares: where things live, the clock, the device, spans.

Nothing here imports the system under test; ``bench/train.py`` and
``bench/serve.py`` do that, and only for the path they time.
"""
from __future__ import annotations

import json
import os
import time
from contextlib import contextmanager
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def process_start() -> float:
    """Wall-clock time at which this process started (``time.time()``
    scale), from ``/proc``; the import time of this module elsewhere."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(line.split()[1]) for line in f
                         if line.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration):
        return _IMPORTED


_IMPORTED = time.time()


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``: a configuration or a traffic mix."""
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no {kind[:-1] if kind.endswith('s') else kind} "
                         f"named {name!r}: {path} is missing")
    return load_json(path)


def reference_model(cfg: dict):
    """The plain reference named by a configuration's ``reference`` key:
    ``bench/reference/<reference>.py``."""
    import importlib
    return importlib.import_module(f"bench.reference.{cfg['reference']}")


def work_model(cfg: dict):
    """The work counts (operations, bytes) for a configuration's model:
    ``bench/work/<reference>.py``."""
    import importlib
    return importlib.import_module(f"bench.work.{cfg['reference']}")


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache`` (a fixed path: the path is part
    of the cache key). Every program is cached, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(n_used: int) -> dict:
    import jax
    devs = jax.devices()[:n_used]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class Compiles:
    """XLA compile seconds and persistent-cache hits, from JAX's monitoring
    events. A compile that starts inside the measured window is counted in
    ``in_window``, which the run reports and the check refuses."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        self.in_window = 0
        self.window_open = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.in_window += int(self.window_open)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


@contextmanager
def span(name: str, enabled: bool):
    """A host span in the profiler's trace (``TraceAnnotation``); free when
    the run is not traced."""
    if not enabled:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


def seed_bits(seed: int, salt: int) -> int:
    """A 32-bit PRNG seed for one stream of the run, from ``--seed`` (which
    may exceed 32 bits) and a salt naming the stream."""
    x = (int(seed) * 0x9E3779B97F4A7C15 + salt * 0xBF58476D1CE4E5B9)
    x &= (1 << 64) - 1
    x ^= x >> 31
    return x & 0x7FFFFFFF
