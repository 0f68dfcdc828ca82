"""From a profiler trace to the numbers the per-layer metrics read.

`load` reads the `.xplane.pb` that `jax.profiler` writes into a plain
dict of events:

    {"device": {plane: [[name, start_ns, dur_ns], ...]},   # device ops
     "host": [[name, start_ns, dur_ns], ...]}              # bench.* spans

`reduce` turns that dict into the traced window's length, the union of
device busy intervals in it (averaged over devices), the device time and
call count of each op name, and the device's idle gaps by the host span
that covers most of each gap.  The harness opens a `bench.window` span
around the measured window and `bench.<what>` spans around what the host
does in it, through `jax.profiler.TraceAnnotation`.
"""

from __future__ import annotations

import re
from collections import defaultdict

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"


def load(path: str) -> dict:
    """Device op events and the benchmark's own host spans of a trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            device[plane.name] = [[ev.name, int(ev.start_ns),
                                   int(ev.duration_ns)]
                                  for ln in lines for ev in ln.events]
        elif plane.name.startswith("/host:"):
            host += [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                     for ln in plane.lines for ev in ln.events
                     if ev.name.startswith(SPAN_PREFIX)]
    return {"device": device, "host": host}


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(events: dict, top: int = 10) -> dict:
    """Window, busy time, ops and idle gaps of a loaded trace (see module
    doc).  Times in nanoseconds; `idle_gaps` and `device_ops` are lists of
    [name, seconds], longest first, at most `top` long."""
    windows = [h for h in events["host"] if h[0] == WINDOW]
    if not windows or not events["device"]:
        return None
    _, w0, wd = windows[0]
    w1 = w0 + wd
    spans = [h for h in events["host"] if h[0] != WINDOW]
    s_name = [h[0] for h in spans]
    s0 = np.array([h[1] for h in spans], np.int64).reshape(-1)
    s1 = s0 + np.array([h[2] for h in spans], np.int64).reshape(-1)
    ops = defaultdict(lambda: [0, 0])
    busy_total, gaps = 0, defaultdict(int)
    for evs in events["device"].values():
        clipped = []
        for name, s, d in evs:
            e = s + d
            if e <= w0 or s >= w1:
                continue
            ops[name][0] += d
            ops[name][1] += 1
            clipped.append((max(s, w0), min(e, w1)))
        busy = _union(clipped)
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            label = "none"
            if spans:
                o = np.minimum(s1, g1) - np.maximum(s0, g0)
                if o.max() > 0:
                    label = s_name[int(o.argmax())]
            gaps[label] += g1 - g0
    n_dev = len(events["device"])
    by_time = sorted(ops.items(), key=lambda kv: -kv[1][0])
    return {
        "window_ns": wd,
        "busy_ns": busy_total / n_dev,
        "device_count": n_dev,
        "ops": {name: {"ns": v[0] / n_dev, "calls": v[1] / n_dev}
                for name, v in ops.items()},
        "device_ops": [[name, v[0] / n_dev / 1e9] for name, v in by_time[:top]],
        "idle_gaps": [[name, ns / n_dev / 1e9] for name, ns in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    }


def kernel_time(reduced: dict, patterns) -> tuple[float, float]:
    """(device ns, calls) of the ops whose name matches any of the
    regular expressions `patterns`."""
    ns = calls = 0.0
    for name, v in reduced["ops"].items():
        if any(re.search(p, name) for p in patterns):
            ns += v["ns"]
            calls += v["calls"]
    return ns, calls
