"""jit'd public wrappers for the fork-join sort kernels.

``device_sort`` / ``device_sort_kv`` run the Pallas network or the XLA
sort as ``kernels/routing.py`` decides from the platform and the operand
dtype (off TPU the kernels run only under ``interpret=True``; see
docs/ARCHITECTURE.md).

Tagged-key stable variants: the bitonic network is not order-preserving,
so the paper's chained-sort lexsort (SU unique filter, §2.3) cannot run
through it directly.  ``device_stable_sort_perm`` packs
``(key - kmin) << tag_bits | lane_index`` into a single int64 so that the
*unstable* bitonic sort of the tagged keys is a *stable* sort of the raw
keys — equal keys order by lane index, i.e. original position.  All
tagged values are distinct, so the low bits of the sorted array ARE the
permutation: no payload lane, half the VMEM traffic of the KV network.
``device_dedup_rows`` chains one tagged sort per column (least-significant
first) to get exactly numpy's stable ``lexsort``, then neighbor-compares.

Width guard: tagging needs ``ceil(log2(cap))`` low bits, so the key span
``kmax - kmin`` must fit the remaining ``63 - tag_bits`` — the *caller*
checks ``fits_tagged_width`` and falls back to the XLA lexsort composite
otherwise (see backend/jax_ops.py).

Incremental merge maintenance: an append to a version-stamped column is
an O(Δ) change, so re-running the full O(N log N) tagged sort per append
wastes exactly the asymptotics the semi-naive fixpoint saves elsewhere.
``device_merge_runs`` merges two individually sorted runs with two rank
launches (``merge_ranks``, the Pallas binary-search kernel) plus one XLA
scatter — final position = own lane + rank in the other run, stable with
left-run-first tie discipline.  ``device_merge_sorted_mirror`` is the
index-maintenance composite built on it: slice the appended tail out of
the resident column buffer, tagged-sort only the tail (O(Δ log Δ)),
re-base the resident tagged run if the key minimum moved, merge, and
de-tag — no host materialization.

Shared sort programs: every composite here is a short chain of jitted
stages around ``device_sort`` / ``device_sort_kv``, whose sorts are
programs of their own.  A sort is the costliest program to compile on
TPU, so each (shape, dtype) compiles one sort that every composite and
caller shares, instead of one sort inlined into every composite
program; XLA sorts under ``XLA_SORT_MIN`` lanes share one padded shape.
"""

import functools

import jax
import jax.numpy as jnp

from repro.kernels.routing import route
from repro.kernels.sortmerge.sortmerge import (bitonic_sort, bitonic_sort_kv,
                                               merge_ranks)


# XLA sorts shorter than this pad up to it: every small sort shares one
# program instead of compiling one per power of two (an XLA sort takes
# tens of seconds to compile for TPU; sorting 2^14 lanes costs little)
XLA_SORT_MIN = 1 << 14


def _pad_top(x, cap: int):
    """``x`` padded to ``cap`` lanes with its dtype's largest value."""
    top = (jnp.iinfo(x.dtype).max if jnp.issubdtype(x.dtype, jnp.integer)
           else jnp.inf)
    return jnp.concatenate([x, jnp.full(cap - x.shape[0], top, x.dtype)])


# the pads and heads around a small sort are programs of their own too
# (one per bucket), so each compiles once per bucket like the sort


@functools.partial(jax.jit, static_argnames=("cap",))
def _pad_keys(x, *, cap: int):
    return _pad_top(x, cap)


@functools.partial(jax.jit, static_argnames=("cap",))
def _pad_kv(keys, vals, *, cap: int):
    """Keys padded with their dtype's largest value, vals with zeros."""
    return _pad_top(keys, cap), jnp.concatenate(
        [vals, jnp.zeros(cap - vals.shape[0], vals.dtype)])


@functools.partial(jax.jit, static_argnames=("n",))
def _head(xs: tuple, *, n: int):
    return tuple(x[:n] for x in xs)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _bitonic(x, *, block: int, interpret: bool):
    return bitonic_sort(x, block=block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _bitonic_kv(keys, vals, *, block: int, interpret: bool):
    return bitonic_sort_kv(keys, vals, block=block, interpret=interpret)


@jax.jit
def _xla_sort(x):
    # a keys-only sort cannot show its stability, and the unstable XLA
    # sort compiles in under half the time for TPU
    return jax.lax.sort(x, is_stable=False)


@jax.jit
def _xla_sort_kv(keys, vals):
    # stable argsort as an unstable sort on (key, lane): the same order,
    # and about half the TPU compile time of jnp.argsort(stable=True)
    lane = jnp.arange(keys.shape[0], dtype=jnp.int32)
    ks, order = jax.lax.sort((keys, lane), num_keys=2, is_stable=False)
    return ks, vals[order]


def device_sort(x: jnp.ndarray, block: int = 1024, force_pallas: bool = False,
                interpret: bool = False) -> jnp.ndarray:
    if route("sort", x, force_pallas=force_pallas, interpret=interpret):
        return _bitonic(x, block=block, interpret=interpret)
    n = x.shape[0]
    if n >= XLA_SORT_MIN:
        return _xla_sort(x)
    return _head((_xla_sort(_pad_keys(x, cap=XLA_SORT_MIN)),), n=n)[0]


def device_sort_kv(keys: jnp.ndarray, vals: jnp.ndarray, block: int = 1024,
                   force_pallas: bool = False, interpret: bool = False):
    """Stable key sort carrying ``vals`` along."""
    if route("sort_kv", keys, force_pallas=force_pallas,
             interpret=interpret):
        return _bitonic_kv(keys, vals, block=block, interpret=interpret)
    n = keys.shape[0]
    if n >= XLA_SORT_MIN:
        return _xla_sort_kv(keys, vals)
    # pad keys sort after every real key (stable: a real key equal to
    # the pad value keeps its place before them)
    return _head(_xla_sort_kv(*_pad_kv(keys, vals, cap=XLA_SORT_MIN)), n=n)


# ---------------------------------------------------------------------------
# Tagged-key stable variants


def tag_bits_for(cap: int) -> int:
    """Low bits needed to tag every lane of a padded buffer of size ``cap``."""
    return max(1, (cap - 1).bit_length())


def fits_tagged_width(kmin: int, kmax: int, cap: int) -> bool:
    """True iff keys spanning [kmin, kmax] can be tagged at buffer size
    ``cap``: the span plus one pad code must fit ``63 - tag_bits`` bits
    (python ints — no intermediate overflow)."""
    span = int(kmax) - int(kmin) + 1  # pad code is span itself -> +1 codes
    return span + 1 <= (1 << (63 - tag_bits_for(cap)))


@functools.partial(jax.jit, static_argnames=("tag_bits",))
def _tag_keys(keys, n_real, kmin, *, tag_bits: int):
    keys = keys.astype(jnp.int64)
    cap = keys.shape[0]
    lane = jnp.arange(cap, dtype=jnp.int64)
    base = jnp.asarray(kmin, jnp.int64)
    # pad lanes get the max representable code for this width, strictly
    # above every real code (the caller's fits_tagged_width guarantees
    # real codes stay <= max_code - 1)
    max_code = (jnp.int64(1) << (63 - tag_bits)) - 1
    return jnp.where(lane < n_real,
                     ((keys - base) << tag_bits) | lane,
                     (max_code << tag_bits) | lane)


@functools.partial(jax.jit, static_argnames=("tag_bits",))
def _detag_keys(s, n_real, kmin, *, tag_bits: int):
    lane = jnp.arange(s.shape[0], dtype=jnp.int64)
    mask = (jnp.int64(1) << tag_bits) - 1
    skeys = jnp.where(lane < n_real,
                      (s >> tag_bits) + jnp.asarray(kmin, jnp.int64),
                      jnp.iinfo(jnp.int64).max)
    return skeys, s & mask


def device_stable_sort_perm(keys: jnp.ndarray, n_real, kmin, *,
                            tag_bits: int, block: int = 1024,
                            force_pallas: bool = False,
                            interpret: bool = False):
    """Stable (sorted keys, permutation) of ``keys[:n_real]``.

    ``keys``: signed integer, padded to a power-of-two ``cap`` (pad
    content is ignored — pad lanes are re-tagged past every real key).
    Narrow code-domain buffers (compressed columns) are widened on
    entry; tagging always runs in int64.  Returns full-``cap`` arrays;
    lanes >= n_real hold int64-max / their own index.
    """
    tagged = _tag_keys(keys, n_real, kmin, tag_bits=tag_bits)
    s = device_sort(tagged, block=block, force_pallas=force_pallas,
                    interpret=interpret)
    return _detag_keys(s, n_real, kmin, tag_bits=tag_bits)


@functools.partial(jax.jit, static_argnames=("cap",))
def _lanes(*, cap: int):
    return jnp.arange(cap, dtype=jnp.int64)


@functools.partial(jax.jit, static_argnames=("ci", "tag_bits"))
def _tag_column(col, order, n_real, kmins, *, ci: int, tag_bits: int):
    cap = col.shape[0]
    lane = jnp.arange(cap, dtype=jnp.int64)
    max_code = (jnp.int64(1) << (63 - tag_bits)) - 1
    k = col[order].astype(jnp.int64)
    return jnp.where(order < n_real,
                     ((k - kmins[ci]) << tag_bits) | lane,
                     (max_code << tag_bits) | lane)


@functools.partial(jax.jit, static_argnames=("tag_bits",))
def _reorder(order, s, *, tag_bits: int):
    return order[s & ((jnp.int64(1) << tag_bits) - 1)]


@jax.jit
def _first_of_runs(cols: tuple, order, n_real):
    cap = cols[0].shape[0]
    diff = jnp.zeros(cap, bool).at[0].set(True)
    for c in cols:
        cs = c[order]
        diff = diff.at[1:].set(diff[1:] | (cs[1:] != cs[:-1]))
    keep = diff & (order < n_real)
    return jnp.where(keep, order, cap), jnp.sum(keep)


def device_dedup_rows(cols: tuple, n_real, kmins: jnp.ndarray, *,
                      tag_bits: int, block: int = 1024,
                      force_pallas: bool = False, interpret: bool = False):
    """SU unique filter over multi-column rows via chained tagged sorts.

    ``cols``: tuple of int64 arrays padded to ``cap``; ``kmins``: int64
    [ncols] per-column minima (host-computed).  Chains one stable tagged
    sort per column, least-significant first — exactly numpy's
    ``lexsort(tuple(reversed(cols)))`` — then keeps the first row of each
    equal run.  Returns (ascending kept row ids padded with ``cap``,
    kept count).
    """
    order = _lanes(cap=cols[0].shape[0])
    for ci in range(len(cols) - 1, -1, -1):
        tagged = _tag_column(cols[ci], order, n_real, kmins, ci=ci,
                             tag_bits=tag_bits)
        s = device_sort(tagged, block=block, force_pallas=force_pallas,
                        interpret=interpret)
        order = _reorder(order, s, tag_bits=tag_bits)
    rows, kept = _first_of_runs(tuple(cols), order, n_real)
    return device_sort(rows, block=block, force_pallas=force_pallas,
                       interpret=interpret), kept


# ---------------------------------------------------------------------------
# Incremental merge maintenance


def _run_ranks(a, b, n_a, n_b, *, block, pallas, interpret):
    """Ranks for a stable two-run merge: for each ``a`` lane the count of
    *real* ``b`` elements strictly below it (side=left), and for each
    ``b`` lane the count of real ``a`` elements at or below it
    (side=right) — a's elements win ties, which is what makes the merge
    of two stable runs equal the full stable sort.  Pad tails must sort
    above every real key on both sides (the searches run over the full
    padded arrays), and ranks are clamped by the other run's real length
    so pad *content* never leaks into positions.  ``pallas``: the
    caller's ``route("merge_ranks", ...)`` decision."""
    if pallas:
        ra = merge_ranks(a, b, side_right=False, block=block,
                         interpret=interpret)
        rb = merge_ranks(b, a, side_right=True, block=block,
                         interpret=interpret)
    else:
        ra = jnp.searchsorted(b, a, side="left").astype(jnp.int32)
        rb = jnp.searchsorted(a, b, side="right").astype(jnp.int32)
    return (jnp.minimum(ra.astype(jnp.int64), n_b),
            jnp.minimum(rb.astype(jnp.int64), n_a))


def device_merge_runs(a, b, n_a, n_b, *, block: int = 1024,
                      force_pallas: bool = False, interpret: bool = False):
    """Bounded two-run merge: ``a[:n_a]`` and ``b[:n_b]`` are each sorted
    ascending; returns one sorted array of ``a.shape[0]`` lanes whose
    real prefix ``[:n_a + n_b]`` is the stable merge (ties keep ``a``
    elements first) and whose pad tail is ``int64 max``.

    Shape contract: the output capacity is ``a.shape[0]`` — the caller
    guarantees ``n_a + n_b <= a.shape[0]`` and pads both inputs with
    ``int64 max`` tails (real keys equal to the sentinel are the
    caller's sentinel-collision guard, as everywhere else in this
    family)."""
    return _merge_runs(a, b, n_a, n_b, block=block, interpret=interpret,
                       pallas=route("merge_ranks", a,
                                    force_pallas=force_pallas,
                                    interpret=interpret))


@functools.partial(jax.jit, static_argnames=("block", "pallas", "interpret"))
def _merge_runs(a, b, n_a, n_b, *, block: int, pallas: bool,
                interpret: bool):
    cap = a.shape[0]
    ra, rb = _run_ranks(a, b, n_a, n_b, block=block, pallas=pallas,
                        interpret=interpret)
    lane_a = jnp.arange(cap, dtype=jnp.int64)
    lane_b = jnp.arange(b.shape[0], dtype=jnp.int64)
    pos_a = jnp.where(lane_a < n_a, lane_a + ra, cap)
    pos_b = jnp.where(lane_b < n_b, lane_b + rb, cap)
    out = jnp.full((cap,), jnp.iinfo(jnp.int64).max, jnp.int64)
    out = out.at[pos_a].set(a, mode="drop")
    out = out.at[pos_b].set(b, mode="drop")
    return out


def _pad_codes(cap: int, tag_bits: int):
    """Per-lane pad codes that sort strictly above every real tagged
    code at this width (``fits_tagged_width`` keeps real high parts
    below ``max_code``)."""
    max_code = (jnp.int64(1) << (63 - tag_bits)) - 1
    return (max_code << tag_bits) | jnp.arange(cap, dtype=jnp.int64)


@functools.partial(jax.jit, static_argnames=("dcap", "tag_bits"))
def _tag_tail(buf, delta_start, n_total, kmin, *, dcap: int, tag_bits: int):
    """The ``dcap``-lane window holding the appended tail, tagged with
    *absolute* lane tags (so the low bits stay the permutation).  The
    window may not fit past ``delta_start`` near the top of the buffer,
    so it slides back and the real rows are masked by their *global*
    lane — pad content on either side is re-tagged away."""
    buf = buf.astype(jnp.int64)  # narrow code buffers widen on entry
    start = jnp.minimum(delta_start, buf.shape[0] - dcap)
    seg = jax.lax.dynamic_slice(buf, (start,), (dcap,))
    gl = jnp.arange(dcap, dtype=jnp.int64) + start
    return jnp.where((gl >= delta_start) & (gl < n_total),
                     ((seg - kmin) << tag_bits) | gl,
                     _pad_codes(dcap, tag_bits))


@functools.partial(jax.jit, static_argnames=(
    "tag_bits", "block", "pallas", "interpret"))
def _merge_tagged(base_tagged, drun, n_run, delta_start, n_total, kmin,
                  kmin_old, *, tag_bits: int, block: int, pallas: bool,
                  interpret: bool):
    cap = base_tagged.shape[0]
    dcap = drun.shape[0]
    d = n_total - delta_start
    n_real = n_run + d
    # re-base the resident run to the new key minimum
    lane = jnp.arange(cap, dtype=jnp.int64)
    lane_d = jnp.arange(dcap, dtype=jnp.int64)
    shift = (kmin_old - kmin) << tag_bits
    base = jnp.where(lane < n_run, base_tagged + shift,
                     _pad_codes(cap, tag_bits))
    # merge (tagged codes are all distinct, so ties cannot occur; the
    # left-first discipline is inherited from device_merge_runs anyway)
    ra, rb = _run_ranks(base, drun, n_run, d, block=block,
                        pallas=pallas, interpret=interpret)
    pos_a = jnp.where(lane < n_run, lane + ra, cap)
    pos_b = jnp.where(lane_d < d, lane_d + rb, cap)
    merged = _pad_codes(cap, tag_bits)
    merged = merged.at[pos_a].set(base, mode="drop")
    merged = merged.at[pos_b].set(drun, mode="drop")
    # de-tag
    mask = (jnp.int64(1) << tag_bits) - 1
    perm = merged & mask
    skeys = jnp.where(lane < n_real, (merged >> tag_bits) + kmin,
                      jnp.iinfo(jnp.int64).max)
    return skeys, perm, merged


def merge_sorted_mirror_impl(buf, base_tagged, n_run, delta_start, n_total,
                             kmin, kmin_old, *, dcap: int, tag_bits: int,
                             block: int = 1024,
                             force_pallas: bool = False,
                             interpret: bool = False):
    """Incremental (sorted, perm) maintenance for an append-only column.

    ``buf``: the resident padded column buffer at the *new* version
    (rows ``[delta_start, n_total)`` are the appended tail).
    ``base_tagged``: the resident sorted run in tagged form — ``(key -
    kmin_old) << tag_bits | row`` for lanes ``< n_run``, pad codes
    above.  ``n_run`` and ``delta_start`` coincide for a full mirror;
    a tombstone-compacted mirror has ``n_run < delta_start`` (the run
    holds only the alive rows of the first ``delta_start`` source rows,
    with *original* row ids in the low bits).  The composite (nothing
    touches the host):

    1. slice the ``dcap``-lane appended tail out of ``buf`` and
       tagged-sort it with *absolute* lane tags (``lane +
       delta_start``) — the O(Δ log Δ) part;
    2. re-base the resident run's codes if the key minimum moved
       (``kmin < kmin_old``: a constant shift of the high part, order
       preserved);
    3. merge the two runs (ranks + scatter, O(N) linear);
    4. de-tag: sorted keys (pads ``int64 max``) + permutation (pads own
       index) — bit-identical to a full stable re-sort of the run's
       rows plus the tail.  The real merged prefix is ``n_run +
       (n_total - delta_start)`` lanes.

    Returns ``(sorted_keys, perm, merged_tagged)`` — the caller stores
    ``merged_tagged`` back as the next resident run.
    """
    drun = _tag_tail(buf, delta_start, n_total, kmin, dcap=dcap,
                     tag_bits=tag_bits)
    drun = device_sort(drun, block=block, force_pallas=force_pallas,
                       interpret=interpret)
    return _merge_tagged(base_tagged, drun, n_run, delta_start, n_total,
                         kmin, kmin_old, tag_bits=tag_bits, block=block,
                         interpret=interpret,
                         pallas=route("merge_ranks", base_tagged,
                                      dtype=jnp.int64,
                                      force_pallas=force_pallas,
                                      interpret=interpret))


def device_merge_sorted_mirror(buf, base_tagged, n_base, n_total, kmin,
                               kmin_old, *, dcap: int, tag_bits: int,
                               block: int = 1024,
                               force_pallas: bool = False,
                               interpret: bool = False):
    """Back-compatible form of ``merge_sorted_mirror_impl`` for full
    (uncompacted) mirrors, where the resident run length and the delta
    window start are the same ``n_base``."""
    return merge_sorted_mirror_impl(
        buf, base_tagged, n_base, n_base, n_total, kmin, kmin_old,
        dcap=dcap, tag_bits=tag_bits, block=block,
        force_pallas=force_pallas, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tag_bits",))
def tagged_from_sorted(skeys, perm, n_real, kmin, *, tag_bits: int):
    """Re-pack a (sorted, perm) mirror into its tagged-run form — the
    seed a full sort leaves behind so the *next* append can take the
    merge path instead of re-sorting."""
    cap = skeys.shape[0]
    lane = jnp.arange(cap, dtype=jnp.int64)
    return jnp.where(lane < n_real, ((skeys - kmin) << tag_bits) | perm,
                     _pad_codes(cap, tag_bits))
