"""jit'd unique filter: sort + mask + bounded compaction (SU pipeline)."""

import functools

import jax
import jax.numpy as jnp

from repro.kernels.routing import use_pallas
from repro.kernels.sortmerge.ops import device_sort
from repro.kernels.uniquefilter.uniquefilter import unique_mask_sorted


@functools.partial(jax.jit, static_argnames=("force_pallas", "interpret"))
def unique_sorted_bounded(x: jnp.ndarray, force_pallas: bool = False,
                          interpret: bool = False):
    """Sort + dedup; returns (values (padded with max), n_unique).

    Narrow integer inputs (code-domain buffers from compressed columns)
    widen to int64 on entry so the mask kernel and the pad sentinel see
    one dtype."""
    if jnp.issubdtype(x.dtype, jnp.integer) and x.dtype != jnp.int64:
        x = x.astype(jnp.int64)
    s = device_sort(x, force_pallas=force_pallas, interpret=interpret)
    if use_pallas("unique_mask", s.dtype, force_pallas=force_pallas,
                  interpret=interpret):
        mask = unique_mask_sorted(s, interpret=interpret)
    else:
        mask = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    big = (jnp.iinfo(x.dtype).max
           if jnp.issubdtype(x.dtype, jnp.integer) else jnp.inf)
    n = jnp.sum(mask)
    # stable compaction: masked-out lanes get the sentinel, then re-sort
    vals = jnp.sort(jnp.where(mask, s, big))
    return vals, n
