"""From a profiler trace (``*.xplane.pb``) to device busy time, the operations
that took most of it, and the longest idle gaps named by the host span that
covers their start.

Reads the trace with nothing but JAX (``jax.profiler.ProfileData``). A device
plane is one whose name starts with ``/device:``; its operations are the events
of the line named ``XLA Ops``. The traced window is the host span
``bench.trace_window`` where the trace holds it, else the extent of the device
events. Host spans are the events of the host planes whose names start with
``bench.``. The device's clock and the host's are not the same clock: on the
v5e of PR 24 a launch's first operation read about a millisecond before the host
span that dispatched it, so a gap's name is good to about that.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.trace_window"
SPAN_PREFIX = "bench."
KERNEL_MARK = "tpu_custom_call"  # in the trace's text of a Pallas kernel's operation
# operations of the ``XLA Ops`` line that move data between chips; the line runs
# one operation at a time, so while one of these runs nothing else does there
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def union_seconds(intervals):
    """Total length of the union of (start, end) intervals, with the merged
    intervals themselves."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def read_planes(path):
    """{'devices': {plane: [(name, start_ns, end_ns)]}, 'spans': [...]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = devices.setdefault(plane.name, [])
                for ev in line.events:
                    evs.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return {"devices": devices, "spans": spans}


def short_name(name, limit=80):
    """An operation's own name and result shape out of the trace's text for
    it: ``fusion.3 bf16[2048,2048]`` from ``%fusion.3 = bf16[2048,2048]{1,0:T(8,128)} fusion(...)``.
    The shape is what tells one fusion from another (float32 scores of
    attention, a convolution's activations)."""
    op, _, rest = name.partition(" = ")
    rest = re.sub(r"\{[^}]*\}", "", rest)  # layouts
    if rest.startswith("("):  # a tuple of results
        shape = rest[:rest.find(")") + 1]
    else:
        shape = rest.split(" ", 1)[0]
    return (op.lstrip("%") + (" " + shape if shape else ""))[:limit]


def _covering_span(spans, t):
    """The shortest benchmark span that covers time t, the window span last."""
    best = None
    for name, s, e in spans:
        if s <= t < e and name != WINDOW_SPAN:
            if best is None or e - s < best[1]:
                best = (name, e - s)
    return best[0] if best else "no_bench_span"


def reduce_planes(planes, top=10, gaps=5):
    """The numbers the result line carries, from what ``read_planes`` read."""
    devices, spans = planes["devices"], planes["spans"]
    devices = {k: v for k, v in devices.items() if v}
    if not devices:
        return None
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(s for evs in devices.values() for _, s, _ in evs)
        hi = max(e for evs in devices.values() for _, _, e in evs)
    busy, by_name, idle, kernel, collective = [], {}, [], 0, 0
    for plane in sorted(devices):
        evs = [(n, max(s, lo), min(e, hi)) for n, s, e in devices[plane]
               if min(e, hi) > max(s, lo)]
        total, merged = union_seconds([(s, e) for _, s, e in evs])
        busy.append(total)
        for n, s, e in evs:
            by_name[short_name(n)] = by_name.get(short_name(n), 0) + (e - s)
            if KERNEL_MARK in n:
                kernel += e - s
            if short_name(n).startswith(COLLECTIVES):
                collective += e - s
        if not idle:  # gaps are named on the first device only
            edge = lo
            for s, e in merged + [[hi, hi]]:
                if s > edge:
                    idle.append((_covering_span(spans, edge), s - edge))
                edge = max(edge, e)
    n = len(devices)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])
    idle.sort(key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": n,
        "op_seconds": {k: v / n / 1e9 for k, v in ops},
        "kernel_s": kernel / n / 1e9,
        "collective_s": collective / n / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in ops[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in idle[:gaps]],
    }


def reduce_trace(trace_dir, top=10, gaps=5):
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_planes(read_planes(path), top=top, gaps=gaps)
