"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
time and calls per device operation, and idle gaps.

A device is a plane named ``/device:TPU:<n>``; its operations are the
events of its ``XLA Ops`` line.  Busy time is the union of those
intervals, clipped to the window.  Host spans are the harness's own
``jax.profiler.TraceAnnotation`` events (names starting ``chipbench.``) on
the host plane; an idle gap of a device is put down to the innermost
harness span that covers its midpoint.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def read_xplane(path: str) -> dict:
    """{"devices": {plane: [(op, start_ns, dur_ns)]},
    "spans": [(name, start_ns, dur_ns)]} from one trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.duration_ns)
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def span_ns(trace: dict, name: str) -> tuple:
    """(start, end) of the harness span ``chipbench.<name>``."""
    for n, s, d in trace["spans"]:
        if n == SPAN_PREFIX + name:
            return s, s + d
    raise KeyError(f"no span {SPAN_PREFIX + name} in the trace")


def reduce(trace: dict, t0_ns: float, t1_ns: float, top: int = 10) -> dict:
    """Busy seconds per device and their mean; seconds and calls per op
    name, summed over devices; and device 0's ``top`` longest idle gaps,
    each with the innermost harness span that covers its midpoint."""
    busy, op_s, op_n, gaps = {}, {}, {}, []
    for i, dev in enumerate(sorted(trace["devices"])):
        ops = [(n, s, s + d) for n, s, d in trace["devices"][dev]
               if s + d > t0_ns and s < t1_ns]
        merged = union(_clip([(s, e) for _, s, e in ops], t0_ns, t1_ns))
        busy[dev] = sum(e - s for s, e in merged) * 1e-9
        for name, s, e in ops:
            dur = (min(e, t1_ns) - max(s, t0_ns)) * 1e-9
            op_s[name] = op_s.get(name, 0.0) + dur
            op_n[name] = op_n.get(name, 0) + 1
        if i == 0:
            edges = [t0_ns] + [x for iv in merged for x in iv] + [t1_ns]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = trace["spans"]
    labelled = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        label, width = "none", float("inf")
        for name, ss, dd in spans:
            if ss <= mid <= ss + dd and dd < width:
                label, width = name[len(SPAN_PREFIX):], dd
        labelled.append((label, (e - s) * 1e-9))
    n = max(len(busy), 1)
    return {
        "window_s": (t1_ns - t0_ns) * 1e-9,
        "busy_s": sum(busy.values()) / n,
        "busy_by_device": busy,
        "op_s": op_s,
        "op_n": op_n,
        "idle_gaps": labelled,
    }
