"""SU unique filter as a Pallas TPU kernel (paper §2.4 deduplication).

On a *sorted* array, an element is first-of-its-run iff it differs from its
predecessor.  The array is laid out as ``(rows, 128)`` tiles (TPU vreg
shape).  Inside a tile the predecessor of lane ``c`` is lane ``c - 1`` of
the same row, and of lane 0 the last lane of the row above — two
``pltpu.roll`` rotations, no gathers.  The only cross-tile dependency is
one element: tile ``i`` reads the last 8 rows of tile ``i-1`` through a
second input ref whose BlockSpec index map is ``max(i*rows/8 - 1, 0)``.
Compaction of the surviving elements is prefix-sum arithmetic done by the
wrapper.

The engine switches on ``jax_enable_x64``, under which Python ints in a
kernel or an index map lower as i64; Mosaic rejects those, so every
scalar here is an explicit ``int32``.  The kernel compiles for TPU at
int32 operands (``kernels/routing.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
DEF_BLOCK = 256 * LANES  # elements per tile


def _unique_mask_kernel(x_ref, prev_ref, m_ref):
    i = pl.program_id(0)
    one = jnp.int32(1)
    x = x_ref[...]
    left = pltpu.roll(x, one, 1)    # left[r, c] = x[r, c-1]; [r, 0] = x[r, 127]
    up = pltpu.roll(left, one, 0)   # up[r, 0] = x[r-1, 127]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    pred = jnp.where(col == 0, up, left)
    m_ref[...] = (x != pred).astype(jnp.int32)
    # lane 0 of row 0 compares with the previous tile's last element
    # (prev_ref holds that tile's last 8 rows); global element 0 is
    # always first-of-run
    p8 = pltpu.roll(pltpu.roll(prev_ref[...], one, 1), one, 0)
    col8 = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
    row8 = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    head = (row8 == 0) & (col8 == 0)
    pred8 = jnp.where(head, p8, pred[:SUBLANES])
    m_ref[:SUBLANES, :] = ((head & (i == 0))
                           | (x[:SUBLANES] != pred8)).astype(jnp.int32)


def unique_mask_sorted(x: jnp.ndarray, block: int = DEF_BLOCK,
                       interpret: bool = False) -> jnp.ndarray:
    """Boolean first-of-run mask for a sorted 1-D integer array.

    ``block`` (elements per tile) rounds up to whole ``(8, 128)`` tiles."""
    n = x.shape[0]
    rows = max(SUBLANES, -(-block // (SUBLANES * LANES)) * SUBLANES)
    tile = rows * LANES
    n_pad = max(tile, -(-n // tile) * tile)
    big = jnp.iinfo(x.dtype).max
    xp = jnp.full((n_pad,), big, x.dtype).at[:n].set(x)
    xp = xp.reshape(n_pad // LANES, LANES)
    step = rows // SUBLANES
    tile_map = lambda i: (i, jnp.int32(0))  # noqa: E731
    prev_map = lambda i: (jnp.maximum(i * step - 1, 0),  # noqa: E731
                          jnp.int32(0))
    mask = pl.pallas_call(
        _unique_mask_kernel,
        grid=(n_pad // tile,),
        in_specs=[pl.BlockSpec((rows, LANES), tile_map),
                  pl.BlockSpec((SUBLANES, LANES), prev_map)],
        out_specs=pl.BlockSpec((rows, LANES), tile_map),
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.int32),
        interpret=interpret,
    )(xp, xp)
    return mask.reshape(-1)[:n] != 0
