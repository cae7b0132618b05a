"""From a profiler trace to device busy time, program time and idle gaps.

``events_from_xplane`` flattens the ``.xplane.pb`` the JAX profiler writes
into plain lists (a plane's name, then per line its name and its events as
``[name, start_ns, duration_ns]``); ``reduce_trace`` works on that form
only, so a small recorded trace in the same form checks it without a chip.

Device planes are those named ``/device:TPU:<n>``.  On them the ``XLA Ops``
line holds one event per operation the device ran (an operation that
contains others, such as a while loop, spans them) and the ``XLA Modules``
line one per program (its jit name and a fingerprint in brackets).  Host
spans the harness opens with ``jax.profiler.TraceAnnotation`` sit on the
host plane's threads; the window's own span, ``bench/window``, fixes the
interval every number is taken over.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench/window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# collective operations as XLA names them on the device
COLLECTIVE = re.compile(r"all-to-all|all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|psum", re.I)
# host spans opened by the harness: "bench/<what the host does>"
HOST_SPAN = re.compile(r"^bench/")


def events_from_xplane(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain lists."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in line.events]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals):
    """Merged, sorted, disjoint ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def module_name(event_name: str) -> str:
    """``jit_solve(1234567)`` -> ``jit_solve``: the program's stable part."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%fusion.35 = f32[...] fusion(...)`` -> ``fusion.35``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce_trace(trace: dict) -> dict | None:
    """Busy and idle time, per-program and collective device time and the
    host's activity in the device's idle gaps, over the ``bench/window``
    span.  None when the trace holds no device operation in the window."""
    window = None
    host_spans = []
    devices = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            devices.append({ln["name"]: ln["events"] for ln in plane["lines"]})
            continue
        for ln in plane["lines"]:
            for name, s, d in ln["events"]:
                if name == WINDOW_SPAN:
                    window = (s, s + d)
                elif HOST_SPAN.match(name):
                    host_spans.append((s, s + d, name[len("bench/"):]))
    if window is None or not devices:
        return None
    lo, hi = window
    busy_ns, modules, ops, collective_ns = [], defaultdict(int), \
        defaultdict(int), 0
    first_busy = None
    for dev in devices:
        spans = _clip([(s, s + d) for _, s, d in dev.get(OPS_LINE, [])],
                      lo, hi)
        merged = _union(spans)
        busy_ns.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
        mods = sorted((s, s + d, module_name(name))
                      for name, s, d in dev.get(MODULES_LINE, []))
        starts = [m[0] for m in mods]
        for s, e, name in mods:
            if e > lo and s < hi:
                modules[name] += e - s
        for name, s, d in dev.get(OPS_LINE, []):
            if s + d > lo and s < hi:
                k = bisect.bisect_right(starts, s) - 1
                owner = mods[k][2] if k >= 0 and s < mods[k][1] else "?"
                ops[f"{owner}/{op_name(name)}"] += d
                if COLLECTIVE.search(name):
                    collective_ns += d
    if not any(busy_ns):
        return None
    n = len(devices)
    gaps = defaultdict(int)
    edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
    cuts = sorted({x for h in host_spans for x in h[:2]})
    for s, e in zip(edges[0::2], edges[1::2]):
        # split the gap where host spans begin or end, and give each piece
        # to the innermost span around it
        bounds = [s] + [x for x in cuts if s < x < e] + [e]
        for a, b in zip(bounds, bounds[1:]):
            if b <= a:
                continue
            mid = (a + b) / 2
            inside = [h for h in host_spans if h[0] <= mid < h[1]]
            label = (min(inside, key=lambda h: h[1] - h[0])[2] if inside
                     else "unattributed")
            gaps[label] += b - a
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "module_s": {k: v / n / 1e9 for k, v in modules.items()},
        "collective_s": collective_ns / n / 1e9,
        "device_ops": top({k: v / n for k, v in ops.items()}),
        "idle_gaps": top(gaps),
    }
