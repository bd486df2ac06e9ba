"""Reduction of a profiler trace to the benchmark's device numbers.

The traced part of a run lies inside the host span ``bench.window``.
Device time is the union of the intervals in which an operation ran on
a device plane (the ``XLA Ops`` line where there is one); the idle
share is one minus that over the window.  Each idle gap is named by the
innermost ``bench.*`` host span around its middle, so a gap says what
the host was doing while the device waited.
"""

from __future__ import annotations

import collections
import glob
import os

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OUTSIDE = "outside bench spans"


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def load(path: str) -> dict:
    """Events of an ``.xplane.pb``: device ops and device programs as
    ``(start_ns, end_ns, name)`` per device plane, and the host's
    ``bench.*`` spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: dict = collections.defaultdict(list)
    programs: dict = collections.defaultdict(list)
    spans = []
    for plane in pd.planes:
        if _is_device(plane.name):
            lines = {line.name: line for line in plane.lines}
            op_line = lines.get("XLA Ops")
            for line in ([op_line] if op_line is not None
                         else list(lines.values())):
                ops[plane.name] += [(e.start_ns, e.end_ns, e.name)
                                    for e in line.events]
            if "XLA Modules" in lines:
                programs[plane.name] += [
                    (e.start_ns, e.end_ns, e.name)
                    for e in lines["XLA Modules"].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.end_ns, e.name) for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"ops": dict(ops), "programs": dict(programs), "spans": spans}


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[start, end)`` intervals clipped to ``[lo, hi)``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _name_gap(spans: list, mid: float) -> str:
    inner = None
    for s, e, name in spans:
        if name != WINDOW and s <= mid < e and (
                inner is None or e - s < inner[1] - inner[0]):
            inner = (s, e, name)
    return inner[2] if inner else OUTSIDE


def reduce_events(ev: dict, top: int = 10) -> dict:
    """Busy and window seconds, idle share, the programs (or ops) that
    took most device time, the longest idle gaps by host span, and the
    device seconds of every program, averaged over device planes."""
    windows = [(s, e) for s, e, name in ev["spans"] if name == WINDOW]
    if not windows or not ev["ops"]:
        return None
    lo, hi = windows[0]
    planes = sorted(ev["ops"])
    busy = 0.0
    gaps = []
    for plane in planes:
        merged = union(ev["ops"][plane], lo, hi)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(b - a, _name_gap(ev["spans"], (a + b) / 2))
                 for a, b in zip(edges[::2], edges[1::2]) if b > a]
    n = len(planes)
    by_name: dict = collections.Counter()
    source = ev["programs"] if ev["programs"] else ev["ops"]
    for plane in planes:
        for s, e, name in source.get(plane, []):
            by_name[name] += max(0, min(e, hi) - max(s, lo)) / 1e9 / n
    gaps.sort(key=lambda g: -g[0])
    idle_by_span: dict = collections.Counter()
    for length, name in gaps:
        idle_by_span[name] += length / 1e9 / n
    window_s = (hi - lo) / 1e9
    busy_s = busy / 1e9 / n
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s else None,
            "device_ops": [[k, v] for k, v in by_name.most_common(top)],
            "program_seconds": dict(by_name),
            "idle_gaps": [[name, length / 1e9] for length, name in gaps[:top]],
            "idle_by_span": dict(idle_by_span)}


def reduce(path: str) -> dict:
    return reduce_events(load(path))
