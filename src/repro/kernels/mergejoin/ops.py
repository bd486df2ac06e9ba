"""jit'd sort-merge join built on the Pallas probe kernel.

``merge_join_bounded`` is the fully-jittable fixed-capacity join used by
the distributed engine.  ``expand_runs`` expands the (lo, hi) runs into
pairs: each left row with candidates marks the output lane where its run
starts (one scatter over the left lanes), and a running max of the marks
(``prefix_max``, a streaming scan) gives every output lane its left row.

``merge_join_gather_bounded`` is the fused device-pipeline form: the same
probe + expansion, but candidate pairs are refined (multi-key / hash
verification) and the joined *payload columns* are gathered and compacted
on device — the ``(li, ri)`` pair arrays never exist on host.  Inputs follow the handle-tier convention (pad lanes are
garbage; real lanes are ``[:n]``), so the keys are re-padded with the join
sentinels inside the program instead of by the caller.

Both joins are two jitted stages around ``device_sort_kv``, which is
jitted on its own so that each (shape, dtype) compiles one sort shared
by every caller (see ``kernels/sortmerge/ops.py``).
"""

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mergejoin.mergejoin import probe_sorted
from repro.kernels.prefix import prefix_max, prefix_sum
from repro.kernels.routing import route
from repro.kernels.sortmerge.ops import device_sort_kv

_I64_MAX = (1 << 63) - 1
_I64_MIN = -(1 << 63)


def _splitmix64_dev(x: jnp.ndarray) -> jnp.ndarray:
    """Device twin of ``backend.base.splitmix64`` (int64 in/out via
    bitcast so values >= 2^63 survive the uint64 round-trip)."""
    z = jax.lax.bitcast_convert_type(x, jnp.uint64)
    z = z + jnp.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return jax.lax.bitcast_convert_type(z ^ (z >> jnp.uint64(31)),
                                        jnp.int64)


@jax.jit
def _widen_with_lanes(keys):
    keys = keys.astype(jnp.int64)
    return keys, jnp.arange(keys.shape[0], dtype=jnp.int32)


def expand_runs(lo, counts, out_cap: int):
    """Expand runs of candidates into ``out_cap`` output lanes.

    Left row ``r`` owns ``counts[r]`` candidates at sorted right
    positions ``lo[r] + j``; they take output lanes ``starts[r] + j``
    with ``starts`` the exclusive prefix sum of ``counts``.  Returns
    ``(row, pos, valid, total)``: each lane's left row and sorted right
    position (int32), ``valid`` on the ``total`` candidate lanes (a
    prefix), and ``total`` itself (may exceed ``out_cap``).

    Each row with candidates writes its id at the lane where its run
    starts (starts of such rows are distinct, so one dropping scatter
    over the left lanes), and a running max of those marks carries it
    over the rest of the run: on the valid lanes that is the row a
    binary search of ``starts`` finds, at streaming cost per lane.
    Lanes past ``total`` hold some row and position; callers mask them.
    """
    counts = counts.astype(jnp.int64)
    starts = prefix_sum(counts) - counts
    total = jnp.sum(counts)
    # a start at or past out_cap owns no lane; clipping keeps the rest
    # exact and everything within int32
    first = jnp.minimum(starts, out_cap).astype(jnp.int32)
    tgt = jnp.where(counts > 0, first, out_cap)
    marks = jnp.zeros(out_cap, jnp.int32).at[tgt].set(
        jnp.arange(counts.shape[0], dtype=jnp.int32), mode="drop")
    row = prefix_max(marks)
    out_idx = jnp.arange(out_cap, dtype=jnp.int32)
    pos = out_idx + (lo.astype(jnp.int32) - first)[row]
    return row, pos, out_idx < total, total


@functools.partial(jax.jit,
                   static_argnames=("out_cap", "block", "pallas",
                                    "interpret"))
def _expand_pairs(l_keys, r_sorted, r_perm, out_cap: int, block: int,
                  pallas: bool, interpret: bool):
    l_keys = l_keys.astype(jnp.int64)
    m = r_sorted.shape[0]
    if pallas:
        lo, hi = probe_sorted(l_keys, r_sorted, block=block,
                              interpret=interpret)
    else:
        lo = jnp.searchsorted(r_sorted, l_keys, side="left").astype(jnp.int32)
        hi = jnp.searchsorted(r_sorted, l_keys, side="right").astype(jnp.int32)
    li, pos, valid, total = expand_runs(lo, hi - lo, out_cap)
    ri = r_perm[jnp.clip(pos, 0, m - 1)]
    return (jnp.where(valid, li, -1), jnp.where(valid, ri, -1), valid, total)


def merge_join_bounded(l_keys: jnp.ndarray, r_keys: jnp.ndarray, out_cap: int,
                       block: int = 1024, force_pallas: bool = False,
                       interpret: bool = False):
    """Equi-join -> (li, ri, valid, total).  li/ri index the *original*
    (unsorted) inputs; up to ``out_cap`` pairs are emitted.  Narrow
    code-domain key buffers (compressed columns) widen on entry."""
    r64, lanes = _widen_with_lanes(r_keys)
    r_sorted, r_perm = device_sort_kv(r64, lanes, block=block,
                                      force_pallas=force_pallas,
                                      interpret=interpret)
    return _expand_pairs(l_keys, r_sorted, r_perm, out_cap=out_cap,
                         block=block, interpret=interpret,
                         pallas=route("probe", l_keys, dtype=jnp.int64,
                                      force_pallas=force_pallas,
                                      interpret=interpret))


@functools.partial(jax.jit, static_argnames=())
def pack_pairs_bounded(li, ri, valid):
    """Pack a bounded join's pair output into one int64 array
    (``li << 32 | ri``) so the host-materializing fallback downloads a
    single transfer.  Pairs are a prefix (``valid`` lanes come first), so
    the caller slices ``[:total]`` before the download."""
    li64 = jnp.where(valid, li, 0).astype(jnp.int64)
    ri64 = jnp.where(valid, ri, 0).astype(jnp.int64)
    return (li64 << 32) | (ri64 & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=())
def device_compact(cols: tuple, mask: jnp.ndarray, n_real):
    """Stable-compact every column to the lanes where ``mask`` holds
    (lanes >= ``n_real`` are pads and never survive).  Returns cap-sized
    arrays whose kept lanes form the prefix, plus the kept count."""
    cap = cols[0].shape[0]
    lane = jnp.arange(cap, dtype=jnp.int64)
    ok = mask & (lane < n_real)
    pos = prefix_sum(ok.astype(jnp.int64)) - 1
    tgt = jnp.where(ok, pos, cap)  # cap is out-of-bounds -> dropped
    outs = tuple(jnp.zeros_like(c).at[tgt].set(c, mode="drop")
                 for c in cols)
    return outs, jnp.sum(ok)


@functools.partial(jax.jit, static_argnames=("hash_keys",))
def _join_domain(l_keys, r_keys, n_l, n_r, hash_keys: bool):
    """Widened keys, join-domain keys re-padded with the join sentinels
    (left MAX / right MIN, so pads can never produce pairs), the right
    lane ids, and the hash-collision flag."""
    l_keys = l_keys.astype(jnp.int64)
    r_keys = r_keys.astype(jnp.int64)
    real_l = jnp.arange(l_keys.shape[0], dtype=jnp.int64) < n_l
    real_r = jnp.arange(r_keys.shape[0], dtype=jnp.int64) < n_r
    if hash_keys:
        lk_dom = _splitmix64_dev(l_keys)
        rk_dom = _splitmix64_dev(r_keys)
        # a real hashed right key equal to the right pad sentinel would
        # let real left keys match pad lanes; the symmetric left case is
        # harmless because left-pad counts are zeroed below
        hash_bad = jnp.any(real_r & (rk_dom == _I64_MIN))
    else:
        lk_dom, rk_dom = l_keys, r_keys
        hash_bad = jnp.asarray(False)
    # handle-tier pads are garbage: re-pad here
    lk = jnp.where(real_l, lk_dom, _I64_MAX)
    rk = jnp.where(real_r, rk_dom, _I64_MIN)
    lanes = jnp.arange(r_keys.shape[0], dtype=jnp.int32)
    return l_keys, r_keys, lk, rk, lanes, hash_bad


@functools.partial(jax.jit,
                   static_argnames=("out_cap", "block", "pallas",
                                    "interpret", "hash_keys"))
def _gather_pairs(l_keys, r_keys, lk, r_sorted, r_perm, hash_bad, n_l,
                  l_pay: tuple, r_pay: tuple, verify_l: tuple,
                  verify_r: tuple, out_cap: int, block: int,
                  pallas: bool, interpret: bool, hash_keys: bool):
    cap_l, cap_r = l_keys.shape[0], r_keys.shape[0]
    real_l = jnp.arange(cap_l, dtype=jnp.int64) < n_l
    if pallas:
        lo, hi = probe_sorted(lk, r_sorted, block=block,
                              interpret=interpret)
    else:
        lo = jnp.searchsorted(r_sorted, lk, side="left").astype(jnp.int32)
        hi = jnp.searchsorted(r_sorted, lk, side="right").astype(jnp.int32)
    # left pads probe MAX and would count pairs whenever a real right key
    # equals MAX; zeroing their counts makes that collision structurally
    # impossible (the remaining guard — a real *left* key equal to the
    # right pad sentinel MIN — is checked by the caller via handle bounds)
    counts = jnp.where(real_l, hi - lo, 0)
    # candidates are emitted as a prefix
    li, pos, valid, total0 = expand_runs(lo, counts, out_cap)
    ri = r_perm[jnp.clip(pos, 0, cap_r - 1)]
    ok = valid
    if hash_keys:
        ok = ok & (l_keys[li] == r_keys[ri])
    for vl, vr in zip(verify_l, verify_r):
        ok = ok & (vl[li] == vr[ri])
    # int32 positions: out_cap, like every bucket, is below 2^31
    pos = prefix_sum(ok.astype(jnp.int32)) - 1
    tgt = jnp.where(ok, pos, out_cap)
    l_out = tuple(jnp.zeros(out_cap, p.dtype).at[tgt].set(p[li],
                                                          mode="drop")
                  for p in l_pay)
    r_out = tuple(jnp.zeros(out_cap, p.dtype).at[tgt].set(p[ri],
                                                          mode="drop")
                  for p in r_pay)
    stats = jnp.stack([jnp.sum(ok), total0,
                       hash_bad.astype(jnp.int64)])
    return l_out, r_out, stats


def merge_join_gather_bounded(l_keys, r_keys, n_l, n_r,
                              l_pay: tuple, r_pay: tuple,
                              verify_l: tuple, verify_r: tuple,
                              out_cap: int, block: int = 1024,
                              force_pallas: bool = False,
                              interpret: bool = False,
                              hash_keys: bool = False):
    """Fused sort-merge join + verify + payload gather.

    Joins ``l_keys[:n_l]`` with ``r_keys[:n_r]`` (``hash_keys`` joins on
    the splitmix64 domain with exact-key verification — the HJ axis),
    refines candidates on the ``(verify_l[i], verify_r[i])`` column pairs
    (multi-key joins), then gathers each payload column at the surviving
    pairs and compacts to a prefix.  Returns

        (l_out, r_out, stats)  with  stats = [total, total0, hash_bad]

    ``total`` — surviving pairs (the real result length), ``total0`` —
    candidate pairs *before* verification (if > ``out_cap`` the caller
    must re-run with a larger capacity: candidates past the cap were
    dropped unverified), ``hash_bad`` — a real hashed key collided with a
    pad sentinel (astronomically rare; caller redoes on host).

    Keys may arrive as narrow code-domain buffers (shared-dictionary
    joins run directly over compressed columns) — widened on entry.
    """
    l64, r64, lk, rk, lanes, hash_bad = _join_domain(
        l_keys, r_keys, n_l, n_r, hash_keys=hash_keys)
    r_sorted, r_perm = device_sort_kv(rk, lanes, block=block,
                                      force_pallas=force_pallas,
                                      interpret=interpret)
    return _gather_pairs(l64, r64, lk, r_sorted, r_perm, hash_bad, n_l,
                         tuple(l_pay), tuple(r_pay), tuple(verify_l),
                         tuple(verify_r), out_cap=out_cap, block=block,
                         interpret=interpret, hash_keys=hash_keys,
                         pallas=route("probe", lk, force_pallas=force_pallas,
                                      interpret=interpret))
