"""Inclusive prefix scans of a 1-D integer array, built to compile fast.

``jnp.cumsum`` and ``lax.cummax`` lower on TPU to associative scans
whose compile takes about a minute at 2^20 int64 lanes (a compile for a
described v5e; the engine compiles one such program per bucketed
shape).  Here one blocked Hillis-Steele scan takes the combining
operator: rows of ``_ROW`` lanes are scanned by log2(_ROW) shifted
combines, then the row totals are scanned the same way and combined
back.  It compiles in about a second at the same size.  The operators
are exact on integers, so ``prefix_sum`` equals ``jnp.cumsum`` and
``prefix_max`` equals ``lax.cummax``.

A row is 128 lanes, the width of a vector register: a 1-D array's tiled
layout is then already that of the rows, so the reshape moves no data.
Rows of 1024 lanes cost a relayout on entry and exit, and for a
described v5e at 2^20 lanes compiled to 1.66 MB of code (int64 sum) and
1.22 MB (int32 max) against 1.10 MB and 1.04 MB at 128 lanes.  Program
code sits in device memory beside the data.
"""

from __future__ import annotations

import jax.numpy as jnp

_ROW = 128


def _scan_rows(x, op, identity):
    """Inclusive scan along the last axis by shifted combines."""
    n = x.shape[-1]
    k = 1
    while k < n:
        pad = jnp.full(x.shape[:-1] + (k,), identity, x.dtype)
        x = op(x, jnp.concatenate([pad, x[..., :-k]], axis=-1))
        k *= 2
    return x


def _scan(x, op, identity):
    """Inclusive scan of a 1-D array under the associative ``op`` whose
    neutral element is ``identity``."""
    n = x.shape[0]
    if n <= _ROW:
        return _scan_rows(x, op, identity)
    rows = -(-n // _ROW)
    xp = jnp.pad(x, (0, rows * _ROW - n),
                 constant_values=identity).reshape(rows, _ROW)
    within = _scan_rows(xp, op, identity)
    upto = _scan(within[:, -1], op, identity)
    before = jnp.concatenate([jnp.full((1,), identity, x.dtype), upto[:-1]])
    return op(within, before[:, None]).reshape(-1)[:n]


def prefix_sum(x):
    """``jnp.cumsum(x)`` for a 1-D integer or boolean array (booleans
    count as int64)."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int64)
    return _scan(x, jnp.add, 0)


def prefix_max(x):
    """``lax.cummax(x)`` for a 1-D integer array."""
    return _scan(x, jnp.maximum, jnp.iinfo(x.dtype).min)
