"""Named spans at the fact engine's layer boundaries.

A span is a ``jax.profiler.TraceAnnotation``: while the profiler runs it
lands in the same trace as the device's programs, on the same clock, so
an idle stretch of the device can be put down to what the host was doing
in it.  While no profiler runs a span costs about a microsecond.  Span
arguments become event stats in the trace; they are counts already at
hand on the host (rows, bytes, the round), never a device value.

``SPANS`` maps every span the engine opens to its layer.
"""

from __future__ import annotations

import functools

SPANS = {
    "hf.load": "loader",
    "hf.infer": "fixpoint driver",
    "hf.round": "fixpoint driver",
    "hf.plan": "fixpoint driver",
    "hf.rule": "island joins",
    "hf.islands": "island joins",
    "hf.join": "island joins",
    "hf.write": "write side",
    "hf.index": "index and residency",
    "hf.d2h": "device backend",
    "hf.h2d": "device backend",
}


@functools.cache
def _annotation():
    import jax.profiler
    return jax.profiler.TraceAnnotation


def span(name: str, **args):
    """A context manager that records ``name`` with ``args`` in the
    profiler's trace; ``set_metadata(**more)`` on it adds arguments
    known only at the end."""
    return _annotation()(name, **args)
