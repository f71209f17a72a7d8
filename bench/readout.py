"""From a run's counts and trace to its result line.

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1`` runs the
window under the profiler and reports the cell's per-layer metrics, each
read by ``bench/metrics/<name>.py`` (a reader that finds nothing to read
returns None, and the metric is left out of the line), with the device's
busy and window seconds and the breakdown of device time and idle gaps.
"""
from __future__ import annotations

import glob
import importlib.util
import shutil
import time

from bench import common


class Tracer:
    """``jax.profiler`` around the measured window, into a fixed directory
    inside the checkout that is emptied before and after."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.path = common.TRACE_DIR
        self.reduced = None
        self._span = None

    def __enter__(self):
        if self.enabled:
            import jax
            shutil.rmtree(self.path, ignore_errors=True)
            jax.profiler.start_trace(str(self.path))
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
        return self

    def __exit__(self, *exc):
        if self.enabled:
            import jax
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return False

    def reduce(self):
        """The reduced trace (``bench/trace.py``), read once."""
        if self.enabled and self.reduced is None:
            from bench import trace
            files = sorted(glob.glob(str(self.path / "**" / "*.xplane.pb"),
                                     recursive=True))
            if not files:
                raise RuntimeError(f"the profiler wrote no trace under "
                                   f"{self.path}")
            self.reduced = trace.reduce(files[-1])
            shutil.rmtree(self.path, ignore_errors=True)
        return self.reduced


def _reader(name: str):
    path = common.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(kind: str) -> dict:
    table = common.load_json(common.BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def result(cell, trace: bool, tracer: Tracer, e2e: dict, counts: dict,
           device: dict, *, correct: bool, attempted: int, failed: int,
           compared: dict) -> dict:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": {}, "device": device}
    if not trace:
        for m in cell["end_to_end"]:
            if m["name"] in e2e:
                out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                             "unit": m["unit"]}
    else:
        t0 = time.perf_counter()
        red = tracer.reduce()
        ctx = {"trace": red, "counts": counts, "e2e": e2e,
               "config": cell["config_data"], "traffic": cell["traffic_data"],
               "peaks": peaks(device["kind"]), "device": device}
        for m in cell["per_layer"]:
            value = _reader(m["name"])(ctx)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["top_ops"],
                            "idle_gaps": red["idle_gaps"]}
        counts["trace_read_s"] = time.perf_counter() - t0
    out["counts"] = counts
    out["compared"] = compared
    return out
