"""Reduce a profiler trace (``.xplane.pb``) to what the readers need.

* the measured window: the host span ``bench.window``;
* per device (planes ``/device:TPU:<n>``), the union of the intervals in
  which an op of the ``XLA Ops`` line ran inside the window: busy seconds,
  averaged over the devices;
* device seconds per op name and per program (``XLA Modules``), averaged
  over the devices;
* the idle gaps of the first device, each named by the host span of the
  benchmark that covers most of it.

``reduce_planes`` takes plain tuples, so the arithmetic is tested without a
chip; ``reduce`` reads a file with ``jax.profiler.ProfileData``.

    python3 bench/trace.py <file.xplane.pb>    # print planes, lines, top ops
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
SPANS = ("dispatch_chunk", "generate", "step", "wait")
# an op that only wraps others (a loop, a branch, a call) would count its
# body twice in per-name sums; it still counts towards busy time
WRAPPER = re.compile(r"^(while|conditional|call)\b")
OP_NAME = re.compile(r"^%?([^\s=]+)")
TOP = 10


def _union(intervals):
    """Merged, sorted ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi):
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def reduce_planes(planes) -> dict:
    """``planes``: ``[(plane_name, [(line_name, [(event, start_ns,
    end_ns), ...]), ...]), ...]``."""
    spans = []
    devices = []
    for pname, lines in planes:
        if pname.startswith("/host:"):
            for _, events in lines:
                spans.extend(ev for ev in events
                             if ev[0] == WINDOW or ev[0] in SPANS)
        elif DEVICE_PLANE.match(pname):
            by_line = dict(lines)
            devices.append((pname, by_line.get(OPS_LINE, []),
                            by_line.get(MODULES_LINE, [])))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = windows[0]
    window_s = (hi - lo) * 1e-9
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    n = max(1, len(devices))
    busy, op_time, mod_time, mod_count = [], defaultdict(float), \
        defaultdict(float), defaultdict(float)
    gaps = []
    for i, (_, ops, mods) in enumerate(devices):
        ops = list(_clip(ops, lo, hi))
        merged = _union([(s, e) for _, s, e in ops])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for name, s, e in ops:
            if not WRAPPER.match(name):
                op_time[name] += (e - s) * 1e-9 / n
        for name, s, e in _clip(mods, lo, hi):
            mod_time[name] += (e - s) * 1e-9 / n
            mod_count[name] += 1.0 / n
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    named = []
    inner = [(nm, s, e) for nm, s, e in spans if nm != WINDOW]
    for s, e in gaps:
        best, cover = "untraced host", 0.0
        for nm, hs, he in inner:
            ov = min(e, he) - max(s, hs)
            if ov > cover:
                best, cover = nm, ov
        named.append([best, (e - s) * 1e-9])
    named.sort(key=lambda g: -g[1])
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_s,
        "devices": len(devices),
        "busy_s": sum(busy) / n if devices else 0.0,
        "busy_by_device": busy,
        "op_time": dict(op_time),
        "module_time": dict(mod_time),
        "module_count": dict(mod_count),
        "top_ops": [[k, v] for k, v in top],
        "idle_gaps": named[:TOP],
        "idle_s_by_span": _sum_by_name(named),
    }


def _sum_by_name(named):
    out = defaultdict(float)
    for name, sec in named:
        out[name] += sec
    return dict(out)


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: the device
    trace names an op by its whole HLO text."""
    m = OP_NAME.match(name)
    return m.group(1) if m else name


def load(path: str):
    """The file's planes in ``reduce_planes``'s form."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(short_name(ev.name), ev.start_ns,
                                       ev.start_ns + ev.duration_ns)
                                      for ev in line.events]))
        planes.append((plane.name, lines))
    return planes


def reduce(path: str) -> dict:
    return reduce_planes(load(path))


def _summary(path: str) -> None:
    for pname, lines in load(path):
        print(f"plane {pname}")
        for lname, events in lines:
            tot = defaultdict(float)
            for name, s, e in events:
                tot[name] += (e - s) * 1e-9
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:8]
            span = (events[0][1], events[-1][2]) if events else None
            print(f"  line {lname!r}: {len(events)} events, span {span}; "
                  f"top {top}")


if __name__ == "__main__":
    _summary(sys.argv[1])
