"""Device idle time split by the program's own spans.

The fact engine opens ``hf.*`` spans at its layer boundaries
(``repro.tracing``); the benchmark opens ``bench.*`` spans around its
calls.  Both land in the profiler's trace on the device's clock.  Every
idle interval of the device inside ``bench.window`` is cut at span
boundaries, and each piece goes to the innermost (shortest) span open
over it on any thread, so a span's number is its self time while the
device waited.  A piece under no ``hf.*`` span is unattributed.

Readers call ``reduction(ctx)``: ``None`` unless the run was traced and
its trace holds ``hf.*`` spans (a program without them reads nothing).
"""

from __future__ import annotations

import collections
import functools
import glob
import heapq
import os

from bench import trace

PREFIX = "hf."
UNATTRIBUTED = "unattributed"


def load(path: str) -> dict:
    """Device ops per device plane as ``(start_ns, end_ns)``, and the
    host's ``hf.*`` and ``bench.*`` spans as ``(start_ns, end_ns, name,
    args)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: dict = collections.defaultdict(list)
    spans = []
    for plane in pd.planes:
        if trace._is_device(plane.name):
            lines = {line.name: line for line in plane.lines}
            op_line = lines.get("XLA Ops")
            for line in ([op_line] if op_line is not None
                         else list(lines.values())):
                ops[plane.name] += [(e.start_ns, e.end_ns)
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.start_ns, e.end_ns, e.name,
                                      dict(e.stats)))
                    elif e.name.startswith(trace.SPAN_PREFIX):
                        spans.append((e.start_ns, e.end_ns, e.name, {}))
    return {"ops": dict(ops), "spans": spans}


def idle_gaps(intervals: list, spans: list, lo: float, hi: float
              ) -> list:
    """Each idle interval of ``[lo, hi)`` outside the merged busy
    ``intervals``, as ``(start, end, {span name: ns})`` by the innermost
    span open over each piece (name ``None``: no span).  ``spans`` are
    ``(start, end, name, ...)``; a sweep over their boundaries keeps the
    open spans in a heap keyed by length."""
    gaps = []
    t = lo
    for s, e in trace.union(intervals, lo, hi):
        if s > t:
            gaps.append((t, s, collections.Counter()))
        t = e
    if hi > t:
        gaps.append((t, hi, collections.Counter()))
    opens = sorted((max(s, lo), min(e, hi), e - s, name)
                   for s, e, name, *_ in spans if min(e, hi) > max(s, lo))
    cuts = sorted({x for a, b, _ in gaps for x in (a, b)}
                  | {x for s, e, *_ in opens for x in (s, e)})
    heap: list = []
    j = k = 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(opens) and opens[j][0] <= a:
            _, e, length, name = opens[j]
            heapq.heappush(heap, (length, e, name))
            j += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        while k < len(gaps) and gaps[k][1] <= a:
            k += 1
        if k < len(gaps) and gaps[k][0] <= a:
            gaps[k][2][heap[0][2] if heap else None] += b - a
    return gaps


def reduce_events(ev: dict, layers: dict, top: int = 10) -> "dict | None":
    """Idle seconds of the traced window by span name and by layer
    (``layers`` maps each ``hf.*`` name to its layer), the idle under no
    ``hf.*`` span, the longest idle gaps named by the span that holds
    most of each, and the ``hf.d2h`` events with their bytes; shares
    are of the window, in percent.  ``None`` without a window, a device
    plane or any ``hf.*`` span."""
    windows = [(s, e) for s, e, name, _ in ev["spans"]
               if name == trace.WINDOW]
    if not windows or not ev["ops"]:
        return None
    lo, hi = windows[0]
    inside = [sp for sp in ev["spans"] if sp[2] != trace.WINDOW
              and sp[1] > lo and sp[0] < hi]
    if not any(sp[2].startswith(PREFIX) for sp in inside):
        return None
    planes = sorted(ev["ops"])
    idle: collections.Counter = collections.Counter()
    gaps = []
    for plane in planes:
        for a, b, by in idle_gaps(ev["ops"][plane], inside, lo, hi):
            idle.update(by)
            gaps.append(((b - a) / 1e9, by.most_common(1)[0][0]))
    gaps.sort(key=lambda g: -g[0])
    window_s = (hi - lo) / 1e9
    by_span = {name or UNATTRIBUTED: ns / 1e9 / len(planes)
               for name, ns in idle.items()}
    by_layer: dict = collections.Counter()
    for name, s in by_span.items():
        by_layer[layers.get(name, UNATTRIBUTED)] += s
    d2h = [args.get("bytes", 0) for s, _, name, args in inside
           if name == "hf.d2h" and s >= lo]
    idle_s = sum(by_span.values())
    return {"window_s": window_s, "idle_s": idle_s,
            "idle_share": 100.0 * idle_s / window_s,
            "by_span": by_span, "by_layer": dict(by_layer),
            "layer_share": {k: 100.0 * v / window_s
                            for k, v in by_layer.items()},
            "top_gaps": [[name or UNATTRIBUTED, length]
                         for length, name in gaps[:top]],
            "infers": sum(1 for sp in inside if sp[2] == "hf.infer"),
            "d2h_calls": len(d2h), "d2h_bytes": sum(d2h),
            "spans": sum(1 for sp in inside if sp[2].startswith(PREFIX))}


def newest_xplane(root: str) -> "str | None":
    """The newest trace under ``<root>/.bench_out/trace/<cell>/``."""
    found = []
    for cell_dir in glob.glob(os.path.join(root, ".bench_out", "trace", "*")):
        try:
            found.append(trace.find_xplane(cell_dir))
        except FileNotFoundError:
            pass
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=4)
def _reduce_file(path: str, mtime: float) -> "dict | None":
    ev = load(path)
    if not any(name.startswith(PREFIX) for _, _, name, _ in ev["spans"]):
        return None  # a program that opens no spans
    from repro.tracing import SPANS
    return reduce_events(ev, SPANS)


def reduction(ctx: dict, root: "str | None" = None) -> "dict | None":
    """The span reduction of this run's trace (read once per process),
    or ``None`` for an untraced run."""
    if not ctx.get("trace"):
        return None
    from bench import harness
    path = newest_xplane(root or harness.ROOT)
    if path is None:
        return None
    return _reduce_file(path, os.path.getmtime(path))


def layer_share(ctx: dict, layer: str) -> "float | None":
    """Percent of the traced window the device sat idle with ``layer``
    the innermost open span's layer."""
    r = reduction(ctx)
    if r is None:
        return None
    return r["layer_share"].get(layer, 0.0)
