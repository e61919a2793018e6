"""Reduce a JAX profiler trace to device busy time, top ops and idle gaps.

The window is the host span the harness opens around its measured
window (``WINDOW_SPAN``). A device is busy while any op of its
``XLA Ops`` line runs; busy time is the union of those intervals inside
the window, averaged over the devices traced. An idle gap is named by the
host event that overlaps it most, so a gap reads as what the host was
doing meanwhile.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
TOP = 10


def load_events(path: str) -> dict:
    """Events of an ``.xplane.pb`` file (or of the newest one under a
    directory) as plain lists: ``device`` maps a device plane to its op
    intervals ``[name, start_ns, end_ns]``; ``host`` lists host events
    ``[thread, name, start_ns, end_ns]``."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        [e.name, e.start_ns, e.start_ns + e.duration_ns]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([line.name, e.name, e.start_ns,
                             e.start_ns + e.duration_ns]
                            for e in line.events if e.duration_ns > 0)
    return {"device": device, "host": host}


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _host_at(host: list, s: float, e: float) -> str:
    """The host event that overlaps [s, e] most (the window span aside)."""
    best, name = 0.0, "host idle"
    for _, ev, hs, he in host:
        if ev == WINDOW_SPAN:
            continue
        ov = min(e, he) - max(s, hs)
        if ov > best:
            best, name = ov, ev
    return name


def reduce(events: dict) -> dict:
    """busy_s, window_s, idle share, the ops that took most time and the
    longest idle gaps (per device, averaged over devices)."""
    spans = [(s, e) for _, name, s, e in events["host"]
             if name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} host span: "
                         "the measured window is unknown")
    devices = events["device"]
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    window_s = (hi - lo) / 1e9
    n_dev = max(1, len(devices))
    busy_ns = 0.0
    op_time: dict[str, float] = defaultdict(float)
    gaps: list[tuple[float, float]] = []
    for i, plane in enumerate(sorted(devices)):
        busy = _union([(s, e) for _, s, e in devices[plane]], lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        for name, s, e in devices[plane]:
            op_time[name] += max(0.0, min(e, hi) - max(s, lo))
        if i == 0:
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    busy_s = busy_ns / n_dev / 1e9
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle": (1.0 - busy_s / window_s) if window_s > 0 else None,
        "device_ops": [[n, t / n_dev / 1e9] for n, t in top_ops],
        "idle_gaps": [[_host_at(events["host"], s, e), (e - s) / 1e9]
                      for s, e in top_gaps],
    }
