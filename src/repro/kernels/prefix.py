"""Inclusive prefix sum of a 1-D integer array, built to compile fast.

``jnp.cumsum`` lowers on TPU to an associative scan whose compile takes
about a minute at 2^20 int64 lanes (a compile for a described v5e; the
engine compiles one such program per bucketed shape).  This form does
the same integer sums — exact, so the result equals ``jnp.cumsum`` —
as a blocked Hillis-Steele scan: rows of ``_ROW`` lanes are scanned by
log2(_ROW) shifted adds, then the row totals are scanned the same way
and added back.  It compiles in about a second at the same size.
"""

from __future__ import annotations

import jax.numpy as jnp

_ROW = 1024


def _scan_rows(x):
    """Inclusive prefix sum along the last axis by shifted adds."""
    n = x.shape[-1]
    k = 1
    while k < n:
        pad = jnp.zeros(x.shape[:-1] + (k,), x.dtype)
        x = x + jnp.concatenate([pad, x[..., :-k]], axis=-1)
        k *= 2
    return x


def prefix_sum(x):
    """``jnp.cumsum(x)`` for a 1-D integer or boolean array (booleans
    count as int64)."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int64)
    n = x.shape[0]
    if n <= _ROW:
        return _scan_rows(x)
    rows = -(-n // _ROW)
    xp = jnp.pad(x, (0, rows * _ROW - n)).reshape(rows, _ROW)
    within = _scan_rows(xp)
    before = prefix_sum(within[:, -1]) - within[:, -1]
    return (within + before[:, None]).reshape(-1)[:n]
