"""Device backend: the inference primitives routed through ``kernels/``.

``JaxOps`` maps each ``Ops`` primitive onto the repo's Pallas fork-join
kernels via their jit'd wrappers:

* ``sort_kv`` / ``sort_perm`` -> ``kernels/sortmerge`` tagged-key stable
  bitonic sort (``(key - kmin) << tag_bits | lane`` packs the original
  position into the low bits, making the unstable network stable and
  letting the sorted low bits double as the permutation — no payload
  lane).
* ``join_pairs``  -> ``kernels/mergejoin`` (sorted probe + bounded expand)
* ``unique_mask`` -> ``kernels/uniquefilter`` (neighbor-compare kernel)
* ``semi_join``   -> sortmerge sort + sorted probe
* ``dedup_rows``  -> chained tagged-key sorts (stable lexsort, §2.3's SU
  filter) + neighbor compare, any column count, all through the Pallas
  sorter.

Width-overflow guard: tagging spends ``ceil(log2(cap))`` low bits, so a
column whose key span needs more than ``63 - tag_bits`` bits cannot be
tagged — those calls fall back to a jitted XLA stable sort / lexsort
composite (still device-resident, just not through the Pallas network).
Inputs whose real keys collide with a pad sentinel on a non-tagged path
take the exact host path — a correctness guard, not a fast path.

Device residency: a ``DeviceArrayCache`` keeps per-fact-type column
buffers, packed join keys, and (sorted, perm) index mirrors resident
across calls, keyed by the owning table's version counter (append-only
columns let a stale buffer be extended by uploading only the tail).
Every host<->device conversion goes through ``self.transfers`` — a
``TransferCounter`` — so residency is measurable: repeated index builds
and write-side dedups at an unchanged version cost zero transfers.

Merge maintenance: resident index mirrors are not re-sorted per append.
Each mirror carries a ``MirrorRuns`` entry (the sorted run in tagged
form); an append sorts only the O(Δ) tail into a delta run and merges it
into the resident run with the bounded two-run merge kernel
(``kernels/sortmerge/ops.device_merge_sorted_mirror``), bit-matching the
full stable re-sort.  Compaction (a full re-sort) triggers when the run
has absorbed ``MIRROR_COMPACT_RUNS`` merges; tombstone churn, tagged
width overflow, and non-append changes force the full-rebuild fallback.
``self.sort_work`` (a ``SortWorkCounter``) splits the device sort bytes
into ``sorted_bytes`` (full sorts) vs ``merged_bytes`` (delta runs) so
"per-append index cost scales with Δ" is measurable in the bench
transfer report.

Shape discipline: inputs are padded to power-of-two buckets with sentinel
keys (``int64 max`` at the tail for sorts, ``int64 min`` on the join's
right side) so the jit cache stays logarithmic in observed sizes.

Modes: ``auto`` lets ``kernels/routing.py`` pick, per call, the Pallas
kernel where it compiles for the platform and operand dtype and the XLA
lowering elsewhere; ``pallas`` forces the compiled Pallas path (TPU) and
raises where it cannot compile; ``interpret`` forces the Pallas kernels
through the interpreter so the full kernel code path runs on CPU
containers (tests / parity checks).  ``route_stats()`` counts kernel
calls per route and the calls that took an exact host fallback.

All device work runs under ``jax.enable_x64(True)`` — fact values
and packed (id, attr) keys are genuine 64-bit — and behind a lock, because
the engine's PF/PW thread pools may issue primitives concurrently.  A
backend built with a ``device`` (one per shard worker) also runs it in
that device's default-device scope, so its uploads, resident columns
and mirrors live there.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import threading

import numpy as np

from repro.backend import codecs
from repro.backend.base import Ops
from repro.backend.device_cache import (DeviceArrayCache, MirrorRuns,
                                        SortWorkCounter, TransferCounter)
from repro.backend.handles import DeviceCol, merge_bounds
from repro.backend.numpy_ops import NumpyOps
from repro.kernels import routing

INT64_MAX = np.iinfo(np.int64).max
INT64_MIN = np.iinfo(np.int64).min


# --------------------------------------------------------------------------
# jitted XLA composites (module level so the jit cache is shared across
# JaxOps instances; shapes are bucketed by the caller)


@functools.lru_cache(maxsize=None)
def _jitted():
    """Lazy import + jit so importing this module without using it stays
    cheap and numpy-only callers never touch jax."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.sortmerge.ops import device_sort

    @functools.partial(jax.jit, static_argnames=())
    def neighbor_mask(x):
        return jnp.concatenate([jnp.ones((1,), bool), x[1:] != x[:-1]])

    @jax.jit
    def member_sorted(keys, s):
        pos = jnp.clip(jnp.searchsorted(s, keys, side="left"),
                       0, s.shape[0] - 1)
        return s[pos] == keys

    def semi_join(keys, bound, block, force_pallas, interpret):
        s = device_sort(bound, block=block, force_pallas=force_pallas,
                        interpret=interpret)
        return member_sorted(keys, s)

    @functools.partial(jax.jit, static_argnames=())
    def stable_sort_perm_xla(keys, n_real):
        """Width-overflow fallback: stable (sorted, perm) via XLA lexsort.
        Pads sort last via an explicit flag, so real keys may hold any
        int64 value including the sentinels."""
        cap = keys.shape[0]
        lane = jnp.arange(cap, dtype=jnp.int64)
        is_pad = lane >= n_real
        order = jnp.lexsort((lane, keys, is_pad))  # last key is primary
        skeys = jnp.where(lane < n_real, keys[order],
                          jnp.iinfo(jnp.int64).max)
        return skeys, order

    @functools.partial(jax.jit, static_argnames=())
    def dedup_rows_xla(cols, n_real):
        """Width-overflow fallback: stable lexsort + neighbor compare."""
        cap = cols[0].shape[0]
        lane = jnp.arange(cap, dtype=jnp.int64)
        is_pad = lane >= n_real
        order = jnp.lexsort((lane,) + tuple(reversed(cols)) + (is_pad,))
        diff = jnp.zeros(cap, bool).at[0].set(True)
        for c in cols:
            cs = c[order]
            diff = diff.at[1:].set(diff[1:] | (cs[1:] != cs[:-1]))
        keep = diff & (order < n_real)
        rows = jnp.sort(jnp.where(keep, order, cap))
        return rows, jnp.sum(keep)

    @functools.partial(jax.jit, static_argnames=())
    def gather(vals, perm):
        return vals[perm]

    @functools.partial(jax.jit, static_argnames=("n",))
    def head(x, n):
        return x[:n]

    @jax.jit
    def repad_max(bound, n_bound):
        lane_b = jnp.arange(bound.shape[0], dtype=jnp.int64)
        return jnp.where(lane_b < n_bound, bound, jnp.iinfo(jnp.int64).max)

    @jax.jit
    def member_sorted_n(keys, s, n_bound):
        pos = jnp.clip(jnp.searchsorted(s, keys, side="left"),
                       0, s.shape[0] - 1)
        return (s[pos] == keys) & (pos < n_bound)

    def semi_join_n(keys, bound, n_bound, block, force_pallas, interpret):
        """Handle-tier semi join: pads are garbage, so the bound side is
        re-padded here and membership is bounded by ``n_bound`` —
        sentinel-value collisions are structurally impossible.  The sort
        is its own program, shared with every other caller."""
        s = device_sort(repad_max(bound, n_bound), block=block,
                        force_pallas=force_pallas, interpret=interpret)
        return member_sorted_n(keys, s, n_bound)

    @functools.partial(jax.jit, static_argnames=())
    def gather_clip(vals, idx):
        return vals[jnp.clip(idx, 0, vals.shape[0] - 1)]

    @functools.partial(jax.jit, static_argnames=())
    def pack_pairs(a, b):
        return (a << 32) | (b & 0xFFFFFFFF)

    @functools.partial(jax.jit, static_argnames=())
    def sort_pairs_xla(keys, vals, n_real):
        """(key, val) rows sorted lexicographically, pads (flag-based)
        last — the probe structure for the write-side exists check."""
        cap = keys.shape[0]
        lane = jnp.arange(cap, dtype=jnp.int64)
        is_pad = lane >= n_real
        order = jnp.lexsort((vals, keys, is_pad))
        mx = jnp.iinfo(jnp.int64).max
        ks = jnp.where(lane < n_real, keys[order], mx)
        vs = jnp.where(lane < n_real, vals[order], mx)
        return ks, vs

    @functools.partial(jax.jit, static_argnames=())
    def fresh_pairs(ks, vs, n_old, kn, vn):
        """For each (kn, vn) row: True iff the pair does NOT appear in
        the sorted (ks, vs) rows — a branch-free binary search of ``vn``
        inside each key's run (the write-side anti-join, no pair
        expansion and therefore no output-capacity retry loop)."""
        cap_old = ks.shape[0]
        klo = jnp.minimum(jnp.searchsorted(ks, kn, side="left"), n_old)
        khi = jnp.minimum(jnp.searchsorted(ks, kn, side="right"), n_old)
        lo, hi = klo, khi
        for _ in range(max(1, cap_old.bit_length()) + 1):
            active = lo < hi
            mid = (lo + hi) // 2
            v = vs[jnp.clip(mid, 0, cap_old - 1)]
            go = v < vn
            lo = jnp.where(active & go, mid + 1, lo)
            hi = jnp.where(active & ~go, mid, hi)
        found = (lo < khi) & (vs[jnp.clip(lo, 0, cap_old - 1)] == vn)
        return ~found

    @functools.partial(
        jax.jit, static_argnames=("block", "use_pallas", "interpret"))
    def batch_probe_j(sk, n_real, probes, block, use_pallas, interpret):
        """Batched rank-1 probe: [lo, hi) run bounds for every probe in
        one launch (Pallas binary-search kernel on TPU).  ``sk`` may be
        a narrow code-domain mirror — widened on entry (probes arrive
        pre-encoded by the caller)."""
        sk = sk.astype(jnp.int64)
        if use_pallas:
            from repro.kernels.mergejoin.mergejoin import probe_sorted
            lo, hi = probe_sorted(probes, sk, block=block,
                                  interpret=interpret)
            lo, hi = lo.astype(jnp.int64), hi.astype(jnp.int64)
        else:
            lo = jnp.searchsorted(sk, probes, side="left").astype(jnp.int64)
            hi = jnp.searchsorted(sk, probes,
                                  side="right").astype(jnp.int64)
        return jnp.stack([jnp.minimum(lo, n_real),
                          jnp.minimum(hi, n_real)])

    def _mix64(x):
        """Device twin of ``base.splitmix64`` (sketch bucketing)."""
        z = jax.lax.bitcast_convert_type(x.astype(jnp.int64), jnp.uint64)
        z = z + jnp.uint64(0x9E3779B97F4A7C15)
        z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
        return z ^ (z >> jnp.uint64(31))

    @functools.partial(jax.jit, static_argnames=("buckets",))
    def sketch_hist(x, n_real, buckets):
        """Cardinality sketch over one padded int64 column: per-bucket
        row counts, per-bucket distinct-value counts, and the distinct
        total.  Pads (>= any real value after the sort) drop out via the
        lane mask; out-of-range bucket ids drop at the scatter."""
        cap = x.shape[0]
        lane = jnp.arange(cap, dtype=jnp.int64)
        valid = lane < n_real
        b = (_mix64(x) % jnp.uint64(buckets)).astype(jnp.int64)
        hist = jnp.zeros(buckets, jnp.int64).at[
            jnp.where(valid, b, buckets)].add(1, mode="drop")
        s = jnp.sort(x)  # pads are INT64_MAX: they sort last
        first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
        newv = first & valid
        db = (_mix64(s) % jnp.uint64(buckets)).astype(jnp.int64)
        dhist = jnp.zeros(buckets, jnp.int64).at[
            jnp.where(newv, db, buckets)].add(1, mode="drop")
        return hist, dhist, jnp.sum(newv)

    @functools.partial(jax.jit, static_argnames=())
    def decode_dict_n(codes, dvals, n_real):
        """Dictionary decode with exact re-pad (sketch input: pads must
        sort last, so garbage pad lanes are re-filled)."""
        lane = jnp.arange(codes.shape[0], dtype=jnp.int64)
        v = dvals[jnp.clip(codes.astype(jnp.int64), 0,
                           dvals.shape[0] - 1)]
        return jnp.where(lane < n_real, v, jnp.iinfo(jnp.int64).max)

    def _decode_lanes(x, vt):
        """Device twin of ``facts.decode_lane_array``: int64 lanes ->
        comparable value domain (ValueType ints are static)."""
        if vt == 5:    # FLOAT: low 32 bits are a float32 pattern
            return jax.lax.bitcast_convert_type(x.astype(jnp.int32),
                                                jnp.float32)
        if vt == 6:    # DOUBLE
            return jax.lax.bitcast_convert_type(x, jnp.float64)
        if vt == 4:    # UINT64
            return jax.lax.bitcast_convert_type(x, jnp.uint64)
        return x

    _CMP = {"==": jnp.equal, "!=": jnp.not_equal,
            ">=": jnp.greater_equal, "<=": jnp.less_equal,
            ">": jnp.greater, "<": jnp.less}

    @functools.partial(jax.jit, static_argnames=("op", "vt"))
    def test_mask(a, b, op, vt):
        """Join-test compare on decoded lanes (Def. 9); pad lanes
        produce garbage mask bits that every consumer masks by n."""
        return _CMP[op](_decode_lanes(a, vt), _decode_lanes(b, vt))

    @functools.partial(jax.jit, static_argnames=("cap",))
    def cross_gather(lcols, rcols, n_r, cap):
        """Cross-product expansion: lane k -> (k // n_r, k % n_r)
        gathers of each payload (pads beyond n_l*n_r are garbage)."""
        idx = jnp.arange(cap, dtype=jnp.int64)
        li = idx // jnp.maximum(n_r, 1)
        ri = idx % jnp.maximum(n_r, 1)
        louts = tuple(c[jnp.clip(li, 0, c.shape[0] - 1)] for c in lcols)
        routs = tuple(c[jnp.clip(ri, 0, c.shape[0] - 1)] for c in rcols)
        return louts, routs

    @functools.partial(jax.jit, static_argnames=())
    def extend_buffer(buf, delta, n_old):
        """Append-only column sync: overwrite [n_old, n_old+len(delta))
        (delta is pre-padded with the buffer's own sentinel, so lanes past
        the new length stay sentinels)."""
        return jax.lax.dynamic_update_slice(buf, delta, (n_old,))

    # -- compressed-column composites (decode on device, never to host) --

    @functools.partial(jax.jit, static_argnames=())
    def widen(x):
        return x.astype(jnp.int64)

    @functools.partial(jax.jit, static_argnames=())
    def decode_for(codes, ref):
        """Frame-of-reference decode; pad lanes stay garbage (handle
        contract: consumers mask by n)."""
        return codes.astype(jnp.int64) + ref

    @functools.partial(jax.jit, static_argnames=())
    def decode_for_n(codes, ref, n_real, fill):
        """Frame-of-reference decode with exact re-pad: lanes past
        ``n_real`` become ``fill`` (for consumers whose pad lanes are
        load-bearing sentinels, e.g. the semi-join bound side)."""
        lane = jnp.arange(codes.shape[0], dtype=jnp.int64)
        return jnp.where(lane < n_real, codes.astype(jnp.int64) + ref,
                         fill)

    @functools.partial(jax.jit, static_argnames=())
    def decode_dict(codes, dvals):
        """Dictionary decode (rank gather); pad lanes garbage."""
        return dvals[jnp.clip(codes.astype(jnp.int64), 0,
                              dvals.shape[0] - 1)]

    @functools.partial(jax.jit, static_argnames=("cap",))
    def decode_rle(values, lengths, cap):
        """Run-length decode; run pads have length 0, decoded pad lanes
        past the real prefix are garbage (repeat's tail fill)."""
        reps = jnp.clip(lengths.astype(jnp.int64), 0, cap)
        return jnp.repeat(values, reps, total_repeat_length=cap)

    @functools.partial(jax.jit, static_argnames=())
    def decode_sorted_for(sk, n_real, ref):
        """Decode a code-domain sorted mirror, re-padding with the sort
        sentinel so the output obeys the sorted-buffer contract."""
        lane = jnp.arange(sk.shape[0], dtype=jnp.int64)
        return jnp.where(lane < n_real, sk + ref,
                         jnp.iinfo(jnp.int64).max)

    @functools.partial(jax.jit, static_argnames=())
    def decode_sorted_dict(sk, n_real, dvals):
        lane = jnp.arange(sk.shape[0], dtype=jnp.int64)
        v = dvals[jnp.clip(sk, 0, dvals.shape[0] - 1)]
        return jnp.where(lane < n_real, v, jnp.iinfo(jnp.int64).max)

    @functools.partial(jax.jit, static_argnames=("dtype",))
    def narrow_sorted(sk, n_real, dtype):
        """Store a code-domain sorted mirror at the codec's width: real
        codes fit by construction, pads re-fill with the dtype max so
        sortedness survives the narrowing (probes run searchsorted over
        the full buffer)."""
        lane = jnp.arange(sk.shape[0], dtype=jnp.int64)
        return jnp.where(lane < n_real, sk,
                         jnp.iinfo(dtype).max).astype(dtype)

    @functools.partial(jax.jit, static_argnames=())
    def dict_crossmap(lvals, rvals, no_match):
        """Cross-dictionary recode table: left rank -> right rank for
        shared values, ``no_match`` (right-domain sentinel) otherwise."""
        rank = jnp.searchsorted(rvals, lvals)
        idx = jnp.clip(rank, 0, rvals.shape[0] - 1)
        return jnp.where(rvals[idx] == lvals, rank, no_match)

    @functools.partial(jax.jit, static_argnames=())
    def map_codes(cmap, codes):
        """Apply a crossmap to a code column (recode the smaller join
        side on device); garbage pad codes clip harmlessly."""
        return cmap[jnp.clip(codes.astype(jnp.int64), 0,
                             cmap.shape[0] - 1)]

    return {"neighbor_mask": neighbor_mask, "semi_join": semi_join,
            "stable_sort_perm_xla": stable_sort_perm_xla,
            "dedup_rows_xla": dedup_rows_xla, "gather": gather,
            "head": head,
            "extend_buffer": extend_buffer, "semi_join_n": semi_join_n,
            "repad_max": repad_max, "member_sorted_n": member_sorted_n,
            "gather_clip": gather_clip, "pack_pairs": pack_pairs,
            "sort_pairs_xla": sort_pairs_xla, "fresh_pairs": fresh_pairs,
            "batch_probe_j": batch_probe_j, "test_mask": test_mask,
            "cross_gather": cross_gather, "widen": widen,
            "decode_for": decode_for, "decode_for_n": decode_for_n,
            "decode_dict": decode_dict,
            "decode_rle": decode_rle,
            "decode_sorted_for": decode_sorted_for,
            "decode_sorted_dict": decode_sorted_dict,
            "narrow_sorted": narrow_sorted,
            "dict_crossmap": dict_crossmap, "map_codes": map_codes,
            "sketch_hist": sketch_hist, "decode_dict_n": decode_dict_n}


class JaxOps(Ops):
    """Bounded-shape, jit-cached, device-resident implementation of
    ``Ops``."""

    # mirror compaction threshold: after this many absorbed delta runs a
    # full re-sort re-establishes the baseline (bounds re-base drift and
    # keeps the tagged run's merge history shallow)
    MIRROR_COMPACT_RUNS = 64

    def __init__(self, mode: str = "auto", block: int = 1024,
                 min_bucket: int | None = None,
                 cache_bytes: int = 256 << 20,
                 compress: bool | None = None, device=None) -> None:
        if mode not in ("auto", "pallas", "interpret"):
            raise ValueError(f"unknown JaxOps mode: {mode!r}")
        self.mode = mode
        self.device = device
        self.interpret = mode == "interpret"
        self.force_pallas = mode in ("pallas", "interpret")
        self.block = block
        self.min_bucket = min_bucket or block
        self.name = f"jax[{mode}]"
        self._host = NumpyOps()  # exact fallback for sentinel collisions
        self._lock = threading.Lock()
        self.transfers = TransferCounter()
        self.sort_work = SortWorkCounter()
        self.cache = DeviceArrayCache(cache_bytes)
        # kernel calls per route ("pallas" / "xla") and exact host
        # fallbacks per primitive (route_stats() reads them)
        self.routes: collections.Counter = collections.Counter()
        self.host_fallbacks: collections.Counter = collections.Counter()
        # compressed device-resident columns: on by default (decoded
        # results are bit-identical by construction); REPRO_COMPRESS=0
        # or compress=False restores raw int64 buffers end to end
        if compress is None:
            compress = codecs.compress_default()
        self.compress = bool(compress)
        # codec accounting (monotone; residency_stats() reads them)
        self._res_counts = {"for": 0, "dict": 0, "rle": 0,
                            "recode_rebuilds": 0, "dict_extends": 0,
                            "decode_calls": 0, "code_joins": 0,
                            "cross_recodes": 0}
        self._dict_bufs: dict[int, object] = {}  # did -> device dictionary

    # -- plumbing ---------------------------------------------------------
    def _bucket(self, n: int) -> int:
        return max(self.min_bucket, 1 << (max(n, 1) - 1).bit_length())

    @staticmethod
    def _delta_bucket(n: int) -> int:
        """Small power-of-two bucket for append deltas (keeps the
        extend_buffer jit cache logarithmic without forcing full-size
        re-uploads for small tails)."""
        return max(32, 1 << (max(n, 1) - 1).bit_length())

    def _x64(self):
        """Scope for device work: x64, this backend's route tally (every
        kernel call's routing decision counts in ``self.routes``), and
        its default device, if it has one."""
        import jax
        stack = contextlib.ExitStack()
        stack.enter_context(jax.enable_x64(True))
        stack.enter_context(routing.tally(self.routes))
        if self.device is not None:
            stack.enter_context(jax.default_device(self.device))
        return stack

    def _host_fallback(self, name: str):
        """Count one exact host-path call of primitive ``name``."""
        self.host_fallbacks[name] += 1
        return self._host

    def route_stats(self) -> dict:
        """Kernel calls per route and host fallbacks per primitive."""
        return {"pallas": self.routes["pallas"],
                "xla": self.routes["xla"],
                "host": dict(self.host_fallbacks)}

    @staticmethod
    def _pad(a: np.ndarray, cap: int, fill: int) -> np.ndarray:
        out = np.full(cap, fill, np.int64)
        out[: len(a)] = a
        return out

    @staticmethod
    def _pad_t(a: np.ndarray, cap: int, fill: int, dtype) -> np.ndarray:
        """Dtype-aware pad for code-domain buffers (codes ship narrow)."""
        out = np.full(cap, fill, dtype)
        out[: len(a)] = a
        return out

    def _dict_dev(self, codec):
        """Device copy of a codec's dictionary, shared per ``did`` (the
        content token) so self-joins and shard views upload it once.
        Caller holds the lock and the x64 scope."""
        if codec is None or codec.values is None:
            return None
        buf = self._dict_bufs.get(codec.did)
        if buf is None:
            if len(self._dict_bufs) > 512:  # dids are content-hashed;
                self._dict_bufs.clear()     # bound stale-token buildup
            buf = self._to_dev(codec.values)
            self._dict_bufs[codec.did] = buf
        return buf

    def _to_dev(self, a: np.ndarray):
        """Upload (counted).  Must run inside the x64 scope or int64
        truncates to int32."""
        import jax
        import jax.numpy as jnp
        self.transfers.count_h2d(a.nbytes)
        if self.device is None:
            return jnp.asarray(a)
        return jax.device_put(a, self.device)

    def _to_host(self, a) -> np.ndarray:
        out = np.asarray(a)
        self.transfers.count_d2h(out.nbytes)
        return out

    def _prefix_to_host(self, a, n: int) -> np.ndarray:
        """Download the first ``n`` lanes of ``a``: a device slice to
        ``n``'s bucket (one program per bucket, not one per length),
        then the exact slice on the host."""
        b = self._bucket(n)
        if b < a.shape[0]:
            a = _jitted()["head"](a, n=b)
        return self._to_host(a)[:n]

    def _sort_args(self) -> dict:
        return {"block": self.block, "force_pallas": self.force_pallas,
                "interpret": self.interpret}

    # -- device-resident column buffers ------------------------------------
    def _colbuf_nbytes(self, value: dict) -> int:
        codec = value["codec"]
        extra = (codec.values.nbytes
                 if codec is not None and codec.values is not None else 0)
        return value["buf"].nbytes + extra

    def _extend_colbuf(self, key, version: int, old: dict,
                       col: np.ndarray, fill: int) -> dict | None:
        """In-place tail extension of a resident column buffer.  Coded
        buffers extend in *code domain*: the tail is encoded with the
        resident codec (dictionary codecs may append-extend their
        dictionary — existing rank codes are untouched, so derived
        mirrors stay valid).  Returns ``None`` when the tail escapes the
        code domain or the capacity — the caller recodes/rebuilds."""
        jt = _jitted()
        n = len(col)
        n_old = old["n"]
        cap = old["buf"].shape[0]
        delta = col[n_old:]
        dcap = self._delta_bucket(len(delta))
        if n > cap or n_old + dcap > cap:
            return None
        codec = old["codec"]
        if codec is None:
            buf = jt["extend_buffer"](
                old["buf"], self._to_dev(self._pad(delta, dcap, fill)),
                n_old)
            value = {"buf": buf, "n": n,
                     "kmin": min(old["kmin"], int(delta.min())),
                     "kmax": max(old["kmax"], int(delta.max())),
                     "codec": None, "dvals": None}
        else:
            enc = codecs.try_encode_delta(codec, delta)
            if enc is None:
                return None
            new_codec, dcodes = enc
            if new_codec.did != codec.did:
                self._res_counts["dict_extends"] += 1
            buf = jt["extend_buffer"](
                old["buf"],
                self._to_dev(self._pad_t(dcodes, dcap,
                                         codec.pad_code(fill),
                                         codec.dtype)),
                n_old)
            value = {"buf": buf, "n": n,
                     "kmin": min(old["kmin"], int(dcodes.min())),
                     "kmax": max(old["kmax"], int(dcodes.max())),
                     "codec": new_codec,
                     "dvals": self._dict_dev(new_codec)}
        self.cache.put(key, version, value, self._colbuf_nbytes(value))
        self.cache.note_extended(key)
        return value

    def _resident_column(self, cache_key, version: int, col: np.ndarray,
                         fill: int, *, encode: bool | None = None,
                         hint: str | None = None) -> dict:
        """Device buffer for an append-only int64 column.

        Returns ``{"buf", "n", "kmin", "kmax", "codec", "dvals"}``.
        With ``codec=None`` the buffer is the raw int64 column padded
        with ``fill`` and ``kmin``/``kmax`` are value bounds.  With a
        codec the buffer holds *codes* in the codec's narrow dtype,
        ``kmin``/``kmax`` are **code-domain** bounds (what the tagged
        sort machinery needs), pads are the codec's code-domain twin of
        ``fill``, and ``dvals`` is the device dictionary (dict codecs).
        A cached entry at an older version whose length is a prefix of
        ``col`` is *extended* — only the appended (encoded) tail is
        uploaded.  ``encode=False`` forces raw (packed join keys span
        >= 2^32 and cannot narrow; the write-side value lane pads with
        0, which is a legal code).  Caller holds the lock and the x64
        scope.
        """
        key = ("colbuf", cache_key, fill)
        n = len(col)
        hit = self.cache.get(key, version)  # counts hit/miss/stale
        if hit is not None and hit["n"] == n:
            return hit
        e = self.cache.get_any(key)
        if (e is not None and e.version < version and e.value["n"] < n):
            value = self._extend_colbuf(key, version, e.value, col, fill)
            if value is not None:
                return value
            if e.value["codec"] is not None:
                self._res_counts["recode_rebuilds"] += 1
        # full (re-)upload: first sight of this column, non-append-only
        # change, capacity growth, or a tail that escaped the code domain
        do_encode = self.compress if encode is None else encode
        codec = payload = None
        if do_encode and n:
            codec, payload = codecs.choose_codec(col, hint=hint)
            # a rebuild whose fresh codec encodes *identically* to the
            # displaced one (same FoR ref+width, or same dictionary
            # content) keeps the old code-domain identity: existing
            # coded state (mirror runs) stays mergeable.  Capacity
            # growth hits this constantly; only true renumberings get a
            # fresh cid.
            if codec is not None and e is not None:
                oldc = e.value["codec"]
                if oldc is not None and codecs.same_code_domain(oldc,
                                                                codec):
                    codec = dataclasses.replace(codec, cid=oldc.cid)
        cap = self._bucket(n)
        if codec is None:
            buf = self._to_dev(self._pad(col, cap, fill))
            value = {"buf": buf, "n": n, "kmin": int(col.min()),
                     "kmax": int(col.max()), "codec": None, "dvals": None}
        else:
            self._res_counts[codec.kind] += 1
            buf = self._to_dev(self._pad_t(payload, cap,
                                           codec.pad_code(fill),
                                           codec.dtype))
            value = {"buf": buf, "n": n, "kmin": int(payload.min()),
                     "kmax": int(payload.max()), "codec": codec,
                     "dvals": self._dict_dev(codec)}
        self.cache.put(key, version, value, self._colbuf_nbytes(value))
        return value

    def _raw_colbuf(self, cv: dict, col: np.ndarray, fill: int):
        """Raw int64 device view of a resident column entry.  A shared
        cache entry may be *coded* even for a caller that passed
        ``encode=False`` — that flag only governs a cold build, while a
        hit (or an append-extend) returns whatever domain another
        consumer cached (``join_pairs`` dict-codes the packed-key
        column).  Coded buffers decode on device; pad lanes refill with
        a sentinel, which is fine for the pad-flag-based consumers
        here.  Caller holds the lock and the x64 scope."""
        codec = cv["codec"]
        if codec is None:
            return cv["buf"]
        jt = _jitted()
        n = cv["n"]
        if codec.kind == "for":
            return jt["decode_for_n"](cv["buf"], codec.ref, n, fill)
        if codec.kind == "dict" and cv["dvals"] is not None:
            self._res_counts["decode_calls"] += 1
            return jt["decode_dict_n"](cv["buf"], cv["dvals"], n)
        # unknown coded shape: transient raw upload
        return self._to_dev(self._pad(col, self._bucket(len(col)), fill))

    # -- primitives -------------------------------------------------------
    def _stable_perm_device(self, buf, n: int, kmin: int, kmax: int):
        """(sorted, perm) device arrays for a padded buffer: tagged-key
        Pallas sort when the key span fits, XLA stable-lexsort fallback
        otherwise.  Caller holds the lock and the x64 scope."""
        from repro.kernels.sortmerge.ops import (device_stable_sort_perm,
                                                 fits_tagged_width,
                                                 tag_bits_for)
        cap = buf.shape[0]
        if fits_tagged_width(kmin, kmax, cap):
            return device_stable_sort_perm(
                buf, n, kmin, tag_bits=tag_bits_for(cap),
                **self._sort_args())
        return _jitted()["stable_sort_perm_xla"](buf, n)

    def _mirror_sort_device(self, cache_key, version: int, buf, n: int,
                            kmin: int, kmax: int, n_dead: int,
                            keys64=None, alive=None, codec=None):
        """(sorted, perm, real length) device arrays for a cached
        mirror, maintained incrementally: when the resident
        ``MirrorRuns`` entry is an append-only prefix of the column at
        an unchanged capacity, only the tail is tagged-sorted
        (O(Δ log Δ)) and merged into the resident run — tombstone
        deltas ride along as carried dead weight (lookups alive-filter
        the perm, so the mirror stays sound); otherwise — cold build,
        capacity growth, width overflow, dead weight past a quarter of
        the alive rows, shrink/rewrite, or the compaction threshold —
        the full sort runs and (when taggable) seeds a fresh run entry.

        Every full-sort event on a tombstoned column (``alive`` given,
        ``n_dead > 0``) **compacts**: only the alive rows are sorted
        (host-gathered, transient upload) and the seeded run maps its
        tag bits back to original row ids, so the mirror — and every
        merge after it — stops carrying dead rows.

        With a ``codec`` the buffer (and therefore the whole mirror)
        lives in code domain: ``kmin``/``kmax`` are code bounds — narrow
        codes are what lets wide-spread columns pass
        ``fits_tagged_width`` — and the resident run remembers the
        codec's ``cid``, refusing to merge across a recode (a recode
        renumbers existing rows, so the old run's tagged codes are in a
        dead domain).  Caller holds the lock and the x64 scope."""
        from repro.kernels.sortmerge.ops import (fits_tagged_width,
                                                 merge_sorted_mirror_impl,
                                                 tag_bits_for,
                                                 tagged_from_sorted)
        cap = buf.shape[0]
        tb = tag_bits_for(cap)
        fits = fits_tagged_width(kmin, kmax, cap)
        cid = codec.cid if codec is not None else 0
        key = ("runs", cache_key)
        ent = self.cache.get_any(key)
        runs = ent.value if ent is not None else None
        compacting = (runs is not None and
                      runs.merges >= self.MIRROR_COMPACT_RUNS)
        # dead rows the resident run still carries: tombstoned since the
        # run last compacted them out.  The mirror stays sound (lookups
        # alive-filter), so bounded churn rides the merge path — only
        # when dead weight passes a quarter of the alive rows does the
        # full-sort fallback compact it away.
        carried = n_dead - runs.n_dead if runs is not None else 0
        churned = runs is not None and (
            carried < 0 or carried * 4 > max(n - n_dead, 1))
        if (runs is not None and fits and not compacting and not churned
                and runs.cap == cap and runs.tag_bits == tb
                and runs.cid == cid
                and runs.src_n < n and runs.kmin >= kmin):
            d = n - runs.src_n
            dcap = self._delta_bucket(d)
            if dcap <= cap:  # the slice window slides back if needed
                sk, perm, merged = merge_sorted_mirror_impl(
                    buf, runs.tagged, runs.n, runs.src_n, n, kmin,
                    runs.kmin, dcap=dcap, tag_bits=tb,
                    **self._sort_args())
                self.cache.put(key, version, MirrorRuns(
                    tagged=merged, n=runs.n + d, kmin=kmin, cap=cap,
                    tag_bits=tb, merges=runs.merges + 1,
                    n_dead=runs.n_dead, src_n=n, cid=cid), merged.nbytes)
                self.sort_work.count_merge(dcap * 8)
                return sk, perm, runs.n + d
        rebuild = (runs is not None and not compacting and
                   (not fits or churned))
        if alive is not None and n_dead > 0 and keys64 is not None:
            # tombstone compaction: sort only the alive rows.  The
            # compacted column is a transient upload (the resident
            # column buffer stays as-is for future merge tail slices);
            # perm maps back to original row ids through the gather.
            rows = np.flatnonzero(np.asarray(alive[:n], bool))
            m = len(rows)
            if m == 0:
                self.cache.invalidate(key)
                self.sort_work.count_full(0, compaction=compacting,
                                          rebuild=rebuild)
                return None, None, 0
            ckeys = keys64[rows]
            ccap = self._bucket(m)
            if codec is not None:
                # stay in code domain so the seeded run matches the
                # resident buffer's domain (same cid as the colbuf)
                ckeys = codecs.encode_with(codec, ckeys).astype(np.int64)
            cbuf = self._to_dev(self._pad(ckeys, ccap, INT64_MAX))
            sk, permc = self._stable_perm_device(
                cbuf, m, int(ckeys.min()), int(ckeys.max()))
            rows_dev = self._to_dev(self._pad(rows.astype(np.int64),
                                              ccap, 0))
            perm = _jitted()["gather"](rows_dev, permc)
            self.sort_work.count_full(ccap * 8, compaction=compacting,
                                      rebuild=rebuild)
            if fits:  # seed a compacted run at the column buffer's cap
                import jax.numpy as jnp
                pad_n = cap - ccap
                if pad_n > 0:
                    sk_f = jnp.concatenate([
                        sk, jnp.full(pad_n, INT64_MAX, jnp.int64)])
                    pm_f = jnp.concatenate([
                        perm, jnp.arange(ccap, cap, dtype=jnp.int64)])
                else:
                    sk_f, pm_f = sk, perm
                tagged = tagged_from_sorted(sk_f, pm_f, m, kmin,
                                            tag_bits=tb)
                self.cache.put(key, version, MirrorRuns(
                    tagged=tagged, n=m, kmin=kmin, cap=cap, tag_bits=tb,
                    merges=0, n_dead=n_dead, src_n=n, cid=cid),
                    tagged.nbytes)
            else:
                self.cache.invalidate(key)
            return sk, perm, m
        sk, perm = self._stable_perm_device(buf, n, kmin, kmax)
        self.sort_work.count_full(cap * 8, compaction=compacting,
                                  rebuild=rebuild)
        if fits:
            tagged = tagged_from_sorted(sk, perm, n, kmin, tag_bits=tb)
            # run holds ALL n rows (nothing compacted out): n_dead=0
            self.cache.put(key, version, MirrorRuns(
                tagged=tagged, n=n, kmin=kmin, cap=cap, tag_bits=tb,
                merges=0, n_dead=0, src_n=n, cid=cid), tagged.nbytes)
        else:
            # width overflow: the XLA-lexsort output has no tagged form
            # to merge into — appends keep re-sorting until the span
            # shrinks (it cannot) or the capacity bucket grows
            self.cache.invalidate(key)
        return sk, perm, n

    def sort_perm(self, keys: np.ndarray, *, cache_key=None,
                  version: int | None = None, n_dead: int = 0,
                  alive=None, hint: str | None = None
                  ) -> tuple[np.ndarray, np.ndarray]:
        keys = np.asarray(keys)
        n = len(keys)
        if n == 0:
            return keys.astype(np.int64), np.empty(0, np.int64)
        use_cache = cache_key is not None and version is not None
        codec = None
        if use_cache:
            hit = self.cache.get(("perm", cache_key), version)
            if hit is not None:
                return hit  # host mirrors: zero transfers
        keys64 = keys.astype(np.int64, copy=False)
        with self._lock, self._x64():
            if use_cache:
                colv = self._resident_column(cache_key, version, keys64,
                                             INT64_MAX, hint=hint)
                buf, kmin, kmax = colv["buf"], colv["kmin"], colv["kmax"]
                codec = colv["codec"]
                sk, perm, n_real = self._mirror_sort_device(
                    cache_key, version, buf, n, kmin, kmax, int(n_dead),
                    keys64=keys64, alive=alive, codec=codec)
                if sk is None:  # fully tombstoned: empty mirror
                    out = (np.empty(0, np.int64), np.empty(0, np.int64))
                    self.cache.invalidate(("permdev", cache_key))
                    self.cache.put(("perm", cache_key), version, out, 0)
                    return out
            elif alive is not None and n_dead:
                # uncached + tombstoned: compact on the host, sort the
                # alive rows, map the perm back to original row ids
                rows = np.flatnonzero(np.asarray(alive[:n], bool))
                if len(rows) == 0:
                    return np.empty(0, np.int64), np.empty(0, np.int64)
                kept = keys64[rows]
                buf = self._to_dev(
                    self._pad(kept, self._bucket(len(rows)), INT64_MAX))
                sk, perm = self._stable_perm_device(
                    buf, len(rows), int(kept.min()), int(kept.max()))
                self.sort_work.count_full(buf.shape[0] * 8)
                n_real = len(rows)
                perm_h = self._to_host(perm)[:n_real].astype(np.int64)
                return (np.ascontiguousarray(self._to_host(sk)[:n_real]),
                        rows[perm_h])
            else:
                kmin, kmax = int(keys64.min()), int(keys64.max())
                buf = self._to_dev(
                    self._pad(keys64, self._bucket(n), INT64_MAX))
                sk, perm = self._stable_perm_device(buf, n, kmin, kmax)
                self.sort_work.count_full(buf.shape[0] * 8)
                n_real = n
            if use_cache:
                # stash the device-side sorted mirror too: batched
                # rank-1 probes (`batch_probe`) search it without ever
                # re-uploading the sorted column (the permutation is
                # consumed host-side only, so it is not pinned).  Coded
                # columns stash the *narrow code-domain* mirror — probes
                # are host-encoded into the same domain — and decode the
                # sorted keys in-program for the host mirror (decoded
                # results stay bit-identical to the raw path).
                if codec is not None:
                    jt = _jitted()
                    sk_store = jt["narrow_sorted"](sk, n_real,
                                                   codec.dtype)
                    self._res_counts["decode_calls"] += 1
                    if codec.kind == "dict":
                        sk = jt["decode_sorted_dict"](sk, n_real,
                                                      colv["dvals"])
                    else:
                        sk = jt["decode_sorted_for"](sk, n_real,
                                                     codec.ref)
                else:
                    sk_store = sk
                self.cache.put(("permdev", cache_key), version,
                               {"sk": sk_store, "perm": None,
                                "n": n_real, "codec": codec},
                               sk_store.nbytes)
            # copy the slices: a view would pin the whole cap-sized base
            # array while the cache accounts only the sliced bytes
            out = (np.ascontiguousarray(self._to_host(sk)[:n_real]),
                   np.ascontiguousarray(self._to_host(perm)[:n_real]))
        if use_cache:
            # hits hand out these exact arrays (aliased into engine index
            # state): freeze them so an in-place write fails loudly
            # instead of corrupting every later hit at this version
            out[0].flags.writeable = False
            out[1].flags.writeable = False
            self.cache.put(("perm", cache_key), version, out,
                           out[0].nbytes + out[1].nbytes)
        return out

    def sort_kv(self, keys: np.ndarray, vals: np.ndarray
                ) -> tuple[np.ndarray, np.ndarray]:
        keys = np.asarray(keys, np.int64)
        vals = np.asarray(vals, np.int64)
        n = len(keys)
        if n == 0:
            return keys.copy(), vals.copy()
        cap = self._bucket(n)
        with self._lock, self._x64():
            kp = self._to_dev(self._pad(keys, cap, INT64_MAX))
            vp = self._to_dev(self._pad(vals, cap, 0))
            sk, perm = self._stable_perm_device(
                kp, n, int(keys.min()), int(keys.max()))
            vs = _jitted()["gather"](vp, perm)
            ks = self._to_host(sk)
            vs = self._to_host(vs)
        return ks[:n], vs[:n]

    def merge_runs(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        """Bounded two-run merge on device (kernels/sortmerge).  No
        sentinel-collision fallback is needed: the rank searches run
        over MAX-padded arrays but are clamped by the runs' real
        lengths, so real keys equal to the sentinel still land in the
        right positions (every real key is <= MAX and the clamp equals
        the true rank)."""
        a = np.asarray(a, np.int64)
        b = np.asarray(b, np.int64)
        n_a, n_b = len(a), len(b)
        if n_a == 0 or n_b == 0:
            return (b if n_a == 0 else a).copy()
        from repro.kernels.sortmerge.ops import device_merge_runs
        cap = self._bucket(n_a + n_b)
        with self._lock, self._x64():
            ap = self._to_dev(self._pad(a, cap, INT64_MAX))
            bp = self._to_dev(
                self._pad(b, self._delta_bucket(n_b), INT64_MAX))
            out = self._to_host(device_merge_runs(
                ap, bp, n_a, n_b, **self._sort_args()))
        return out[: n_a + n_b]

    def join_pairs(self, lkeys: np.ndarray, rkeys: np.ndarray, *,
                   rkeys_key=None, rkeys_version: int | None = None
                   ) -> tuple[np.ndarray, np.ndarray]:
        lkeys = np.asarray(lkeys, np.int64)
        rkeys = np.asarray(rkeys, np.int64)
        n, m = len(lkeys), len(rkeys)
        if n == 0 or m == 0:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        # left pads (MAX) must not match real right keys and right pads
        # (MIN) must not match real left keys
        if lkeys.min() == INT64_MIN or rkeys.max() == INT64_MAX:
            return self._host_fallback("join_pairs").join_pairs(lkeys,
                                                                rkeys)
        import jax  # noqa: F401  (ensures backend init before lock)
        from repro.kernels.mergejoin.ops import merge_join_bounded
        cap = self._bucket(max(n, m))
        use_cache = rkeys_key is not None and rkeys_version is not None
        with self._lock, self._x64():
            # conversions live inside enable_x64 or int64 truncates to int32
            if use_cache:
                colv = self._resident_column(rkeys_key, rkeys_version,
                                             rkeys, INT64_MIN)
                rp = colv["buf"]
                if colv["codec"] is not None:
                    # right side is resident in code domain: translate
                    # the probe keys into the same domain instead of
                    # decoding the resident buffer.  Absent left keys
                    # become ``no_match_code`` (> every real code, <
                    # both pad sentinels), which matches nothing — the
                    # raw path's answer.
                    lkeys = codecs.encode_probes(colv["codec"], lkeys)
            else:
                rp = self._to_dev(
                    self._pad(rkeys, self._bucket(m), INT64_MIN))
            lp = self._to_dev(self._pad(lkeys, self._bucket(n), INT64_MAX))
            while True:
                li, ri, valid, total = merge_join_bounded(
                    lp, rp, out_cap=cap, block=self.block,
                    force_pallas=self.force_pallas,
                    interpret=self.interpret)
                total = int(total)
                if total <= cap:
                    break
                cap = self._bucket(total)  # one retry: exact total known
            if total == 0:
                return np.empty(0, np.int64), np.empty(0, np.int64)
            # valid pairs are a prefix: pack (li << 32 | ri) on device and
            # download the prefix once — one transfer, not three
            from repro.kernels.mergejoin.ops import pack_pairs_bounded
            packed = self._prefix_to_host(
                pack_pairs_bounded(li, ri, valid), total)
        return packed >> 32, packed & 0xFFFFFFFF

    def _narrow_h2d(self, a: np.ndarray, cap: int, fill: int,
                    lo: int, hi: int):
        """Upload an int64 array through a frame-of-reference narrowing
        when ``[lo, hi]`` fits a smaller dtype, then widen back on
        device (transient-transfer compression: the affine shift is
        exact, and the widened buffer restores the original values with
        lanes past the real prefix re-padded to ``fill``).  Falls back
        to the raw upload.  Caller holds the lock and the x64 scope."""
        dt = codecs.smallest_dtype(hi - lo) if self.compress else None
        if dt is None:
            return self._to_dev(self._pad(a, cap, fill))
        nar = self._to_dev(self._pad_t((a - lo).astype(dt), cap,
                                       np.iinfo(dt).max, dt))
        return _jitted()["decode_for_n"](nar, lo, len(a), fill)

    def unique_mask(self, sorted_keys: np.ndarray) -> np.ndarray:
        x = np.asarray(sorted_keys, np.int64)
        n = len(x)
        if n == 0:
            return np.zeros(0, bool)
        # tail pads never influence mask lanes < n, so no sentinel guard
        with self._lock, self._x64():
            xp = self._narrow_h2d(x, self._bucket(n), INT64_MAX,
                                  int(x[0]), int(x[-1]))
            if routing.route("unique_mask", xp,
                             force_pallas=self.force_pallas,
                             interpret=self.interpret):
                from repro.kernels.uniquefilter.uniquefilter import \
                    unique_mask_sorted
                mask = unique_mask_sorted(xp, block=self.block,
                                          interpret=self.interpret)
            else:
                mask = _jitted()["neighbor_mask"](xp)
            mask = self._to_host(mask)
        return mask[:n]

    def semi_join(self, keys: np.ndarray, bound_values: np.ndarray
                  ) -> np.ndarray:
        keys = np.asarray(keys, np.int64)
        bound = np.asarray(bound_values, np.int64)
        n, m = len(keys), len(bound)
        if n == 0 or m == 0:
            return np.zeros(n, bool)
        if keys.max() == INT64_MAX:  # would match the bound-side pads
            return self._host_fallback("semi_join").semi_join(keys, bound)
        with self._lock, self._x64():
            kp = self._narrow_h2d(keys, self._bucket(n), INT64_MAX,
                                  int(keys.min()), int(keys.max()))
            bp = self._narrow_h2d(bound, self._bucket(m), INT64_MAX,
                                  int(bound.min()), int(bound.max()))
            mask = self._to_host(_jitted()["semi_join"](
                kp, bp, block=self.block, force_pallas=self.force_pallas,
                interpret=self.interpret))
        return mask[:n]

    def dedup_rows(self, cols: list[np.ndarray]) -> np.ndarray:
        from repro.kernels.sortmerge.ops import (device_dedup_rows,
                                                 fits_tagged_width,
                                                 tag_bits_for)
        cols = [np.asarray(c, np.int64) for c in cols]
        n = len(cols[0])
        if n == 0:
            return np.empty(0, np.int64)
        cap = self._bucket(n)
        spans = [(int(c.min()), int(c.max())) for c in cols]
        tagged_ok = all(fits_tagged_width(lo, hi, cap) for lo, hi in spans)
        if not tagged_ok and any(hi == INT64_MAX for _, hi in spans):
            # the XLA fallback is pad-flag based and sentinel-safe, but a
            # width overflow AND a sentinel collision means genuinely
            # adversarial keys: take the exact host path
            return self._host_fallback("dedup_rows").dedup_rows(cols)
        import jax.numpy as jnp
        with self._lock, self._x64():
            padded = tuple(self._to_dev(self._pad(c, cap, INT64_MAX))
                           for c in cols)
            if tagged_ok:
                kmins = self._to_dev(np.asarray([lo for lo, _ in spans],
                                                np.int64))
                rows, count = device_dedup_rows(
                    padded, n, kmins, tag_bits=tag_bits_for(cap),
                    **self._sort_args())
            else:
                rows, count = _jitted()["dedup_rows_xla"](
                    padded, jnp.asarray(n))
            count = int(self._to_host(count))
            rows = self._to_host(rows)[:count]
        return rows.astype(np.int64)

    # -- handle tier (device-resident, uid-memoized) -----------------------
    # Every method below keeps its result on device inside a ``DeviceCol``
    # and memoizes it in the ``DeviceArrayCache`` keyed by the operand
    # handles' uids.  Handles are immutable and uids are never reused, so
    # a memo hit is sound — and it is what makes a *repeated* island
    # evaluation at a fixed table version cost zero transfers and zero
    # device work: the same cached input handles map to the same cached
    # output handles all the way through joins, semi-joins, dedup, and
    # the write-side anti-join.

    prefer_handles = True

    @staticmethod
    def _memoable(*handles) -> bool:
        """Memoize only chains built from stable handles — an op with a
        transient operand (delta-window state) can never see the same
        uids again, so a memo entry would be a guaranteed-dead miss."""
        return all(h.stable for h in handles)

    def _memo_get(self, key):
        return self.cache.get(("hmemo",) + key, 0)

    def _memo_put(self, key, value, nbytes: int):
        self.cache.put(("hmemo",) + key, 0, value, int(nbytes))
        return value

    def _empty_h(self) -> DeviceCol:
        e = np.empty(0, np.int64)
        return DeviceCol(e, 0, self, host=e)

    @staticmethod
    def _handles_nbytes(out) -> int:
        """Device bytes held by a (lout, rout, n) join result — memo
        accounting for the host-fallback path."""
        lout, rout, _ = out
        return sum(getattr(h.data, "nbytes", 0) for h in lout + rout)

    @staticmethod
    def _fit_cap(data, cap: int):
        """Eagerly align a device buffer to ``cap`` lanes (pad lanes are
        garbage by contract, so zero-fill is fine)."""
        import jax.numpy as jnp
        cur = data.shape[0]
        if cur == cap:
            return data
        if cur > cap:
            return data[:cap]
        return jnp.concatenate([data, jnp.zeros(cap - cur, data.dtype)])

    def _upload_locked(self, arr) -> DeviceCol:
        arr = np.ascontiguousarray(np.asarray(arr, np.int64))
        n = len(arr)
        if n == 0:
            return self._empty_h()
        # small columns (delta slices, append frontiers) pad to a small
        # power-of-two bucket — h2d bytes scale with Δ, not with the
        # kernel block (the device programs re-pad internally, so a
        # sub-block cap is legal everywhere handles flow)
        buf = self._to_dev(self._pad(arr, self._delta_bucket(n), 0))
        return DeviceCol(buf, n, self, int(arr.min()), int(arr.max()),
                         host=arr)

    def upload(self, arr) -> DeviceCol:
        with self._lock, self._x64():
            return self._upload_locked(arr)

    def upload_resident(self, cache_key, version: int, arr,
                        assume_prefix: bool = False,
                        transient: bool = False) -> DeviceCol:
        """Delta-only upload of an append-frontier column (semi-naive
        eval): the device buffer for ``cache_key`` stays resident across
        versions, and when the cached state is a prefix of ``arr`` —
        rows appended at the frontier, nothing rewritten — only the tail
        goes up via ``dynamic_update_slice``.  The returned handle is
        stable per ``(cache_key, version)``, so downstream uid-keyed
        memos keep hitting between appends."""
        arr = np.ascontiguousarray(np.asarray(arr, np.int64))
        n = len(arr)
        if n == 0:
            return self._empty_h()
        if transient:
            # one-shot window: no resident entry could ever be reused,
            # so upload straight and poison downstream memoization
            with self._lock, self._x64():
                h = self._upload_locked(arr)
            h.stable = False
            return h
        key = ("rescol", cache_key)
        hit = self.cache.get(key, version)
        if hit is not None and hit.n == n:
            return hit
        jt = _jitted()
        with self._lock, self._x64():
            e = self.cache.get_any(key)
            if e is not None and e.value.n < n:
                old = e.value
                n_old = old.n
                delta = arr[n_old:]
                dcap = self._delta_bucket(len(delta))
                prefix_ok = old.bounds_known() and (
                    assume_prefix or (
                        old._host is not None and
                        np.array_equal(arr[:n_old], old._host[:n_old])))
                if prefix_ok and old.codec is not None:
                    h = self._extend_res_coded(key, version, old, arr,
                                               delta, dcap)
                    if h is not None:
                        return h
                    self._res_counts["recode_rebuilds"] += 1
                elif prefix_ok:
                    cap = old.data.shape[0]
                    if n <= cap and n_old + dcap <= cap:
                        buf = jt["extend_buffer"](
                            old.data,
                            self._to_dev(self._pad(delta, dcap, 0)),
                            n_old)
                        lo = min(int(delta.min()), old.lo)
                        hi = max(int(delta.max()), old.hi)
                        h = DeviceCol(buf, n, self, lo, hi, host=arr)
                        self.cache.put(key, version, h, buf.nbytes)
                        self.cache.note_extended(key)
                        return h
            h = self._upload_res_locked(arr)
        self.cache.put(key, version, h, self._res_nbytes(h))
        return h

    def _res_nbytes(self, h: DeviceCol) -> int:
        """Cache-accounted bytes of a resident handle: the *coded*
        footprint (plus the dictionary).  A forced decode materializes a
        transient int64 buffer on top — that working set is deliberately
        not accounted (it dies with the handle)."""
        if h.codec is None:
            return getattr(h._data, "nbytes", 0)
        if h.codec.kind == "rle":
            return h.codes["v"].nbytes + h.codes["l"].nbytes
        extra = (h.codec.values.nbytes
                 if h.codec.values is not None else 0)
        return h.codes.nbytes + extra

    def _decode_thunk(self, codec, codes, dvals):
        """Deferred device-side decode for a coded resident handle.
        Runs at most once, on first ``.data`` access; takes NO backend
        lock (it can fire inside a locked region) and opens its own x64
        scope (it can equally fire outside one)."""
        jt = _jitted()

        def thunk():
            with self._x64():
                self._res_counts["decode_calls"] += 1
                if codec.kind == "for":
                    return jt["decode_for"](codes, codec.ref)
                if codec.kind == "dict":
                    return jt["decode_dict"](codes, dvals)
                return jt["decode_rle"](codes["v"], codes["l"],
                                        cap=codes["cap"])
        return thunk

    def _coded_handle(self, arr, codec, codes, host) -> DeviceCol:
        dvals = self._dict_dev(codec) if codec.kind == "dict" else None
        return DeviceCol(None, len(arr), self, int(arr.min()),
                         int(arr.max()), host=host, codec=codec,
                         codes=codes,
                         thunk=self._decode_thunk(codec, codes, dvals))

    def _upload_res_locked(self, arr) -> DeviceCol:
        """Resident-column upload: codes when an exact codec beats raw
        int64 (RLE allowed — resident frontiers are often run-heavy
        derived columns), raw otherwise.  The handle keeps the code
        buffer + codec visible (``h.codes`` / ``h.codec``) so joins can
        run in code domain; the int64 view decodes lazily on device.
        Caller holds the lock and the x64 scope."""
        n = len(arr)
        codec = payload = None
        if self.compress and n >= 16:
            codec, payload = codecs.choose_codec(arr, allow_rle=True,
                                                 min_n=16)
        if codec is None:
            return self._upload_locked(arr)
        self._res_counts[codec.kind] += 1
        cap = self._delta_bucket(n)
        if codec.kind == "rle":
            values, lengths = payload
            rcap = self._delta_bucket(codec.nruns)
            codes = {"v": self._to_dev(self._pad(values, rcap, 0)),
                     "l": self._to_dev(self._pad_t(
                         lengths, rcap, 0, np.dtype(np.int32))),
                     "cap": cap}
        else:
            codes = self._to_dev(self._pad_t(payload, cap, 0,
                                             codec.dtype))
        return self._coded_handle(arr, codec, codes, arr)

    def _extend_res_coded(self, key, version: int, old: DeviceCol,
                          arr: np.ndarray, delta: np.ndarray,
                          dcap: int) -> DeviceCol | None:
        """Code-domain tail extension of a coded resident column: only
        the encoded tail ships.  Dictionary growth rides the append-only
        dictionary extension (existing rank codes untouched — same
        ``cid``); RLE appends run pairs (non-maximal runs are sound).
        Returns ``None`` when the tail escapes the code domain or the
        capacity — the caller recode-rebuilds.  Caller holds the lock
        and the x64 scope."""
        jt = _jitted()
        n, n_old = len(arr), old.n
        codec = old.codec
        enc = codecs.try_encode_delta(codec, delta)
        if enc is None:
            return None
        new_codec, payload = enc
        if codec.kind == "rle":
            rcap = old.codes["v"].shape[0]
            cap = old.codes["cap"]
            values, lengths = payload
            rdcap = self._delta_bucket(len(values))
            if n > cap or codec.nruns + rdcap > rcap:
                return None
            codes = {"v": jt["extend_buffer"](
                         old.codes["v"],
                         self._to_dev(self._pad(values, rdcap, 0)),
                         codec.nruns),
                     "l": jt["extend_buffer"](
                         old.codes["l"],
                         self._to_dev(self._pad_t(
                             lengths, rdcap, 0, np.dtype(np.int32))),
                         codec.nruns),
                     "cap": cap}
        else:
            cap = old.codes.shape[0]
            if n > cap or n_old + dcap > cap:
                return None
            if new_codec.did != codec.did:
                self._res_counts["dict_extends"] += 1
            codes = jt["extend_buffer"](
                old.codes,
                self._to_dev(self._pad_t(payload, dcap, 0,
                                         codec.dtype)),
                n_old)
        h = self._coded_handle(arr, new_codec, codes, arr)
        self.cache.put(key, version, h, self._res_nbytes(h))
        self.cache.note_extended(key)
        return h

    def cross_join_h(self, lpay, rpay, n_l: int, n_r: int):
        total = n_l * n_r
        if total == 0:
            return ([self._empty_h() for _ in lpay],
                    [self._empty_h() for _ in rpay], 0)
        memo = self._memoable(*lpay, *rpay)
        key = ("cross", tuple(p.uid for p in lpay),
               tuple(p.uid for p in rpay), n_l, n_r)
        if memo:
            hit = self._memo_get(key)
            if hit is not None:
                return hit
        cap = self._bucket(total)
        with self._lock, self._x64():
            louts, routs = _jitted()["cross_gather"](
                tuple(p.data for p in lpay), tuple(p.data for p in rpay),
                n_r, cap=cap)
        lout = [DeviceCol(d, total, self, p.lo, p.hi, stable=memo)
                for d, p in zip(louts, lpay)]
        rout = [DeviceCol(d, total, self, p.lo, p.hi, stable=memo)
                for d, p in zip(routs, rpay)]
        out = (lout, rout, total)
        if memo:
            return self._memo_put(
                key, out, sum(d.nbytes for d in louts)
                + sum(d.nbytes for d in routs))
        return out

    def test_mask_h(self, a: DeviceCol, b: DeviceCol, op: str,
                    valtype: int) -> DeviceCol:
        if a.n == 0:
            e = np.zeros(0, bool)
            return DeviceCol(e, 0, self, host=e)
        memo = self._memoable(a, b)
        key = ("tm", a.uid, b.uid, op, int(valtype))
        if memo:
            hit = self._memo_get(key)
            if hit is not None:
                return hit
        with self._lock, self._x64():
            buf = _jitted()["test_mask"](
                a.data, self._fit_cap(b.data, a.data.shape[0]),
                op=op, vt=int(valtype))
        h = DeviceCol(buf, a.n, self, stable=memo)
        if memo:
            return self._memo_put(key, h, buf.nbytes)
        return h

    def materialize(self, h: DeviceCol) -> np.ndarray:
        if isinstance(h.data, np.ndarray):
            return h.data[: h.n]
        with self._lock, self._x64():
            return self._prefix_to_host(h.data, h.n)

    def iota_h(self, n: int) -> DeviceCol:
        if n == 0:
            return self._empty_h()
        hit = self._memo_get(("iota", n))
        if hit is not None:
            return hit
        import jax.numpy as jnp
        with self._lock, self._x64():
            buf = jnp.arange(self._bucket(n), dtype=jnp.int64)
        h = DeviceCol(buf, n, self, 0, n - 1,
                      host=np.arange(n, dtype=np.int64))
        return self._memo_put(("iota", n), h, buf.nbytes)

    def const_h(self, value: int, n: int) -> DeviceCol:
        if n == 0:
            return self._empty_h()
        value = int(value)
        hit = self._memo_get(("const", value, n))
        if hit is not None:
            return hit
        import jax.numpy as jnp
        with self._lock, self._x64():
            buf = jnp.full(self._bucket(n), value, jnp.int64)
        h = DeviceCol(buf, n, self, value, value,
                      host=np.full(n, value, np.int64))
        return self._memo_put(("const", value, n), h, buf.nbytes)

    def concat_h(self, parts) -> DeviceCol:
        parts = [self.as_handle(p) for p in parts]
        live = [p for p in parts if p.n] or parts[:1]
        if len(live) == 1:
            return live[0]
        memo = self._memoable(*live)
        key = ("cat",) + tuple(p.uid for p in live)
        if memo:
            hit = self._memo_get(key)
            if hit is not None:
                return hit
        import jax.numpy as jnp
        total = sum(p.n for p in live)
        if total <= self.block:
            # small batches (delta-round action columns): device concat
            # would jit-compile every new piece-shape combination, so
            # host-concat + one delta-bucket upload is strictly cheaper
            out = np.concatenate([p.host() for p in live])
            h = self.upload(out)
            h.stable = memo
            if memo:
                return self._memo_put(key, h,
                                      getattr(h.data, "nbytes", 0))
            return h
        with self._lock, self._x64():
            pieces = [p.data[: p.n] if not isinstance(p.data, np.ndarray)
                      else self._to_dev(p.data[: p.n]) for p in live]
            cap = self._bucket(total)
            if cap > total:
                pieces.append(jnp.zeros(cap - total, jnp.int64))
            buf = jnp.concatenate(pieces)
        lo, hi = merge_bounds(*live)
        h = DeviceCol(buf, total, self, lo, hi, stable=memo)
        if memo:
            return self._memo_put(key, h, buf.nbytes)
        return h

    def gather_h(self, col: DeviceCol, idx: DeviceCol,
                 n: int | None = None) -> DeviceCol:
        n = idx.n if n is None else n
        if n == 0 or col.n == 0:
            return self._empty_h()
        memo = self._memoable(col, idx)
        key = ("g", col.uid, idx.uid, n)
        if memo:
            hit = self._memo_get(key)
            if hit is not None:
                return hit
        with self._lock, self._x64():
            buf = _jitted()["gather_clip"](col.data, idx.data)
        h = DeviceCol(buf, n, self, col.lo, col.hi, stable=memo)
        if memo:
            return self._memo_put(key, h, buf.nbytes)
        return h

    def select_mask_h(self, cols, mask: DeviceCol):
        n = cols[0].n
        if n == 0:
            return [self._empty_h() for _ in cols], 0
        memo = self._memoable(mask, *cols)
        key = ("sel", tuple(c.uid for c in cols), mask.uid)
        if memo:
            hit = self._memo_get(key)
            if hit is not None:
                return hit
        from repro.kernels.mergejoin.ops import device_compact
        with self._lock, self._x64():
            cap = mask.data.shape[0]
            datas = tuple(self._fit_cap(c.data, cap) for c in cols)
            outs, cnt = device_compact(datas, mask.data, n)
            kept = int(self._to_host(cnt))
        handles = [DeviceCol(d, kept, self, c.lo, c.hi, stable=memo)
                   for d, c in zip(outs, cols)]
        if memo:
            return self._memo_put(key, (handles, kept),
                                  sum(d.nbytes for d in outs))
        return handles, kept

    def semi_join_h(self, keys: DeviceCol, bound: DeviceCol) -> DeviceCol:
        if keys.n == 0:
            e = np.zeros(0, bool)
            return DeviceCol(e, 0, self, host=e)
        memo = self._memoable(keys, bound)
        key = ("sj", keys.uid, bound.uid)
        if memo:
            hit = self._memo_get(key)
            if hit is not None:
                return hit
        import jax.numpy as jnp
        with self._lock, self._x64():
            if bound.n == 0:
                buf = jnp.zeros(keys.data.shape[0], bool)
            else:
                buf = _jitted()["semi_join_n"](
                    keys.data, bound.data, bound.n, block=self.block,
                    force_pallas=self.force_pallas,
                    interpret=self.interpret)
        h = DeviceCol(buf, keys.n, self, stable=memo)
        if memo:
            return self._memo_put(key, h, buf.nbytes)
        return h

    def pack_pairs_h(self, a: DeviceCol, b: DeviceCol) -> DeviceCol:
        if a.n == 0:
            return self._empty_h()
        memo = self._memoable(a, b)
        key = ("pp", a.uid, b.uid)
        if memo:
            hit = self._memo_get(key)
            if hit is not None:
                return hit
        with self._lock, self._x64():
            buf = _jitted()["pack_pairs"](
                a.data, self._fit_cap(b.data, a.data.shape[0]))
        lo = hi = None
        if a.lo is not None and a.hi is not None:
            lo, hi = (a.lo << 32), (a.hi << 32) | 0xFFFFFFFF
        h = DeviceCol(buf, a.n, self, lo, hi, stable=memo)
        if memo:
            return self._memo_put(key, h, buf.nbytes)
        return h

    def join_gather_h(self, lkeys: DeviceCol, rkeys: DeviceCol,
                      lpay, rpay, verify=(), algo: str = "MJ"):
        if algo not in ("MJ", "HJ"):
            raise ValueError(f"unknown join algo: {algo!r}")
        verify = list(verify)
        if lkeys.n == 0 or rkeys.n == 0:
            return ([self._empty_h() for _ in lpay],
                    [self._empty_h() for _ in rpay], 0)
        memo = self._memoable(lkeys, rkeys, *lpay, *rpay,
                              *(a for a, _ in verify),
                              *(b for _, b in verify))
        key = ("jg", algo, lkeys.uid, rkeys.uid,
               tuple(p.uid for p in lpay), tuple(p.uid for p in rpay),
               tuple((a.uid, b.uid) for a, b in verify))
        if memo:
            hit = self._memo_get(key)
            if hit is not None:
                return hit
        hash_keys = algo == "HJ"
        # code-domain join: when both key columns encode equal values to
        # equal codes (same join token — same-table self-joins and shard
        # views share dictionaries by content), join directly over the
        # narrow code buffers and never decode either side.  Two dict
        # columns with *different* dictionaries recode the smaller side
        # on device through a rank-to-rank crossmap (absent values map
        # to the target's never-matching code).  Both paths are sound
        # for HJ too: splitmix of a code is a consistent hash domain and
        # the in-program exact check compares codes, which is value
        # equality under the shared encoding.
        lt = codecs.join_token(lkeys.codec)
        rt = codecs.join_token(rkeys.codec)
        code_join = lt is not None and lt == rt
        cross_dict = (not code_join
                      and lkeys.codec is not None
                      and rkeys.codec is not None
                      and lkeys.codec.kind == "dict"
                      and rkeys.codec.kind == "dict")
        # a real left key equal to the right pad sentinel would match pad
        # lanes (MJ only; the hash domain is checked inside the program).
        # Code-domain keys can't reach the sentinels (reserved headroom
        # at both dtype ends), so the guard only applies to raw keys.
        if (not hash_keys and not code_join and not cross_dict
                and (lkeys.lo is None or lkeys.lo == INT64_MIN)):
            out = self._join_gather_host(lkeys, rkeys, lpay, rpay,
                                         verify, algo)
            for h in out[0] + out[1]:
                h.stable = memo
            if memo:
                return self._memo_put(key, out, self._handles_nbytes(out))
            return out
        from repro.kernels.mergejoin.ops import merge_join_gather_bounded
        cap = self._bucket(max(lkeys.n, rkeys.n))
        bad = False
        with self._lock, self._x64():
            jt = _jitted()
            if code_join:
                lkb, rkb = lkeys.codes, rkeys.codes
                self._res_counts["code_joins"] += 1
            elif cross_dict:
                self._res_counts["cross_recodes"] += 1
                if lkeys.n <= rkeys.n:
                    cmap = jt["dict_crossmap"](
                        self._dict_dev(lkeys.codec),
                        self._dict_dev(rkeys.codec),
                        rkeys.codec.no_match_code)
                    lkb = jt["map_codes"](cmap, lkeys.codes)
                    rkb = rkeys.codes
                else:
                    cmap = jt["dict_crossmap"](
                        self._dict_dev(rkeys.codec),
                        self._dict_dev(lkeys.codec),
                        lkeys.codec.no_match_code)
                    lkb = lkeys.codes
                    rkb = jt["map_codes"](cmap, rkeys.codes)
            else:
                lkb, rkb = lkeys.data, rkeys.data
            cap_l = lkb.shape[0]
            cap_r = rkb.shape[0]
            lp = tuple(self._fit_cap(p.data, cap_l) for p in lpay)
            rp = tuple(self._fit_cap(p.data, cap_r) for p in rpay)
            vl = tuple(self._fit_cap(a.data, cap_l) for a, _ in verify)
            vr = tuple(self._fit_cap(b.data, cap_r) for _, b in verify)
            while True:
                louts, routs, stats = merge_join_gather_bounded(
                    lkb, rkb, lkeys.n, rkeys.n, lp, rp,
                    vl, vr, out_cap=cap, block=self.block,
                    force_pallas=self.force_pallas,
                    interpret=self.interpret, hash_keys=hash_keys)
                st = self._to_host(stats)
                total, total0, bad = int(st[0]), int(st[1]), bool(st[2])
                if bad or total0 <= cap:
                    break
                cap = self._bucket(total0)  # one retry: exact total known
        if bad:
            out = self._join_gather_host(lkeys, rkeys, lpay, rpay,
                                         verify, algo)
            for h in out[0] + out[1]:
                h.stable = memo
            if memo:
                return self._memo_put(key, out, self._handles_nbytes(out))
            return out
        lout = [DeviceCol(d, total, self, p.lo, p.hi, stable=memo)
                for d, p in zip(louts, lpay)]
        rout = [DeviceCol(d, total, self, p.lo, p.hi, stable=memo)
                for d, p in zip(routs, rpay)]
        if memo:
            return self._memo_put(
                key, (lout, rout, total),
                sum(d.nbytes for d in louts) + sum(d.nbytes
                                                   for d in routs))
        return lout, rout, total

    def _join_gather_host(self, lkeys, rkeys, lpay, rpay, verify, algo):
        """Exact host path for sentinel-adversarial keys (downloads and
        re-uploads — counted; correctness guard, not a fast path)."""
        li, ri = self._host_fallback("join_gather").join(
            lkeys.host(), rkeys.host(), algo)
        for vl, vr in verify:
            if len(li) == 0:
                break
            ok = vl.host()[li] == vr.host()[ri]
            li, ri = li[ok], ri[ok]
        lout = [self.upload(p.host()[li]) for p in lpay]
        rout = [self.upload(p.host()[ri]) for p in rpay]
        return lout, rout, len(li)

    def dedup_select_h(self, cols):
        n = cols[0].n
        if n == 0:
            return self._empty_h(), 0
        memo = self._memoable(*cols)
        key = ("dd", tuple(c.uid for c in cols))
        if memo:
            hit = self._memo_get(key)
            if hit is not None:
                return hit
        from repro.kernels.sortmerge.ops import (device_dedup_rows,
                                                 fits_tagged_width,
                                                 tag_bits_for)
        import jax.numpy as jnp
        with self._lock, self._x64():
            cap = cols[0].data.shape[0]
            datas = tuple(self._fit_cap(c.data, cap) for c in cols)
            tagged = (all(c.bounds_known() for c in cols) and
                      all(fits_tagged_width(c.lo, c.hi, cap)
                          for c in cols))
            if tagged:
                # both paths ignore pad *content* (tagging rewrites pad
                # lanes by position; the XLA fallback is pad-flag based),
                # so no sentinel-collision host fallback exists here
                kmins = self._to_dev(
                    np.asarray([c.lo for c in cols], np.int64))
                rows, cnt = device_dedup_rows(
                    datas, n, kmins, tag_bits=tag_bits_for(cap),
                    **self._sort_args())
            else:
                rows, cnt = _jitted()["dedup_rows_xla"](
                    datas, jnp.asarray(n))
            kept = int(self._to_host(cnt))
        h = DeviceCol(rows, kept, self, 0 if kept else None,
                      (n - 1) if kept else None, stable=memo)
        if memo:
            return self._memo_put(key, (h, kept), rows.nbytes)
        return h, kept

    def fresh_mask_h(self, key_new: DeviceCol, vals_new: DeviceCol,
                     old_keys, old_vals, cache_uid=None,
                     version: int | None = None) -> DeviceCol:
        n_new = key_new.n
        if n_new == 0:
            e = np.zeros(0, bool)
            return DeviceCol(e, 0, self, host=e)
        use_cache = cache_uid is not None and version is not None
        # the table-side sorted pairs stay resident either way; only the
        # output mask memo needs stable batch operands
        memo = use_cache and self._memoable(key_new, vals_new)
        key = ("fm", key_new.uid, vals_new.uid, cache_uid, version)
        if memo:
            hit = self._memo_get(key)
            if hit is not None:
                return hit
        import jax.numpy as jnp
        jt = _jitted()
        old_keys = np.asarray(old_keys, np.int64)
        old_vals = np.asarray(old_vals, np.int64)
        with self._lock, self._x64():
            if len(old_keys) == 0:
                buf = jnp.ones(key_new.data.shape[0], bool)
            else:
                pkv = (self.cache.get(("pkv", cache_uid), version)
                       if use_cache else None)
                if pkv is None:
                    if use_cache:
                        # encode=False governs a *cold build* only: the
                        # probe side below arrives raw, so a fresh
                        # upload must stay raw too.  But the ("pk", uid)
                        # entry is shared with ``join_pairs`` (engine
                        # dedup / retraction joins), which dict-codes it
                        # under compression — a hit or an append-extend
                        # of that entry comes back *coded*, so decode to
                        # raw on device before sorting.
                        kb = self._resident_column(
                            ("pk", cache_uid), version, old_keys,
                            INT64_MIN, encode=False)
                        vb = self._resident_column(
                            ("vals", cache_uid), version, old_vals, 0,
                            encode=False)
                        kraw = self._raw_colbuf(kb, old_keys, INT64_MIN)
                        vraw = self._raw_colbuf(vb, old_vals, 0)
                        cap_o = max(kraw.shape[0], vraw.shape[0])
                        kbuf = self._fit_cap(kraw, cap_o)
                        vbuf = self._fit_cap(vraw, cap_o)
                    else:
                        cap_o = self._bucket(len(old_keys))
                        kbuf = self._to_dev(
                            self._pad(old_keys, cap_o, INT64_MIN))
                        vbuf = self._to_dev(self._pad(old_vals, cap_o, 0))
                    ks, vs = jt["sort_pairs_xla"](kbuf, vbuf,
                                                  len(old_keys))
                    pkv = {"ks": ks, "vs": vs, "n": len(old_keys)}
                    if use_cache:
                        self.cache.put(("pkv", cache_uid), version, pkv,
                                       ks.nbytes + vs.nbytes)
                buf = jt["fresh_pairs"](
                    pkv["ks"], pkv["vs"], pkv["n"], key_new.data,
                    self._fit_cap(vals_new.data,
                                  key_new.data.shape[0]))
        h = DeviceCol(buf, n_new, self, stable=memo)
        if memo:
            self._memo_put(key, h, buf.nbytes)
        return h

    def residency_stats(self) -> dict:
        """Footprint report for the compressed-resident tier: actual
        (coded) bytes vs what the same resident columns would occupy as
        raw int64 buffers, plus the codec event counters.  Transient
        buffers (probe uploads, join outputs) and derived mirrors are
        out of scope — the ratio measures the *storage* tier the codecs
        replace."""
        from repro.backend.handles import DeviceCol
        out = {"resident_bytes_raw": 0, "resident_bytes_coded": 0,
               "columns_raw": 0, "columns_coded": 0,
               "codecs": dict(self._res_counts),
               "compress": self.compress}
        with self.cache._lock:
            entries = [(k, e.value) for k, e in self.cache._entries.items()]
        for key, v in entries:
            fam = key[0] if isinstance(key, tuple) else None
            if fam == "colbuf" and isinstance(v, dict) and "buf" in v:
                coded = self._colbuf_nbytes(v)
                raw = v["buf"].shape[0] * 8
                if v["codec"] is None:
                    out["columns_raw"] += 1
                else:
                    out["columns_coded"] += 1
            elif fam == "rescol" and isinstance(v, DeviceCol):
                coded = self._res_nbytes(v)
                if v.codec is None:
                    raw = coded
                    out["columns_raw"] += 1
                else:
                    cap = (v.codes["cap"] if v.codec.kind == "rle"
                           else v.codes.shape[0])
                    raw = cap * 8
                    out["columns_coded"] += 1
            else:
                continue
            out["resident_bytes_raw"] += raw
            out["resident_bytes_coded"] += coded
        return out

    def resident_devices(self) -> set:
        """The devices that hold this backend's cached device buffers
        (resident columns, mirrors, memoized handles)."""
        import jax
        with self.cache._lock:
            todo = [e.value for e in self.cache._entries.values()]
        out: set = set()
        while todo:
            v = todo.pop()
            if isinstance(v, jax.Array):
                out |= v.devices()
            elif isinstance(v, dict):
                todo.extend(v.values())
            elif isinstance(v, (list, tuple)):
                todo.extend(v)
            elif isinstance(v, DeviceCol):
                todo.extend((v._data, v.codes))
            elif dataclasses.is_dataclass(v):
                todo.extend(getattr(v, f.name)
                            for f in dataclasses.fields(v))
        return out

    def batch_probe(self, sorted_keys, probes, *, cache_key=None,
                    version: int | None = None):
        probes = np.asarray(probes, np.int64)
        n = len(probes)
        m = len(sorted_keys)
        if n == 0 or m == 0:
            return np.zeros(n, np.int64), np.zeros(n, np.int64)
        use_cache = cache_key is not None and version is not None
        with self._lock, self._x64():
            ent = (self.cache.get(("permdev", cache_key), version)
                   if use_cache else None)
            if ent is None:
                sk = np.ascontiguousarray(
                    np.asarray(sorted_keys, np.int64))
                buf = self._to_dev(
                    self._pad(sk, self._bucket(m), INT64_MAX))
                n_real = m
                if use_cache:
                    self.cache.put(("permdev", cache_key), version,
                                   {"sk": buf, "perm": None, "n": m,
                                    "codec": None},
                                   buf.nbytes)
            else:
                buf, n_real = ent["sk"], ent["n"]
                codec = ent.get("codec")
                if codec is not None:
                    # the resident mirror holds narrow codes: translate
                    # the probes into the same domain (absent values map
                    # to ``no_match_code``, whose [lo, hi) is empty —
                    # exactly the raw path's answer).  The searchsorted
                    # clamps by ``n_real`` keep out-of-range codes sound.
                    probes = codecs.encode_probes(codec, probes)
            pd = self._to_dev(self._pad(probes, self._bucket(n),
                                        INT64_MAX))
            res = self._to_host(_jitted()["batch_probe_j"](
                buf, n_real, pd, block=self.block,
                use_pallas=routing.route("probe", pd,
                                         force_pallas=self.force_pallas,
                                         interpret=self.interpret),
                interpret=self.interpret))
        return res[0, :n].copy(), res[1, :n].copy()

    def sketch(self, col, *, cache_key=None, version: int | None = None):
        """Device cardinality sketch (see ``Ops.sketch``).  The sketch
        itself is tiny (~1KB) and cached per ``(uid, data_version)``; a
        miss prefers the *resident coded column* over a fresh upload —
        decode-on-device, histogram, and one small d2h.  RLE columns
        (and cache misses without a resident buffer) upload the host
        column transiently."""
        from repro.backend.base import SKETCH_BUCKETS
        col = np.asarray(col, np.int64)
        n = len(col)
        use_cache = cache_key is not None and version is not None
        if n == 0:
            return super().sketch(col)
        with self._lock, self._x64():
            if use_cache:
                hit = self.cache.get(("sketch", cache_key), version)
                if hit is not None:
                    return hit
            jt = _jitted()
            buf = None
            if use_cache:
                ent = self.cache.get_any(
                    ("colbuf", (cache_key[0], cache_key[1], ""),
                     INT64_MAX))
                cv = ent.value if ent is not None else None
                if (isinstance(cv, dict) and cv.get("n") == n
                        and "buf" in cv):
                    codec = cv["codec"]
                    if codec is None:
                        buf = cv["buf"]  # raw, pads already INT64_MAX
                    elif codec.kind == "for":
                        buf = jt["decode_for_n"](cv["buf"], codec.ref, n,
                                                 INT64_MAX)
                    elif codec.kind == "dict" and cv["dvals"] is not None:
                        buf = jt["decode_dict_n"](cv["buf"], cv["dvals"],
                                                  n)
                        self._res_counts["decode_calls"] += 1
            if buf is None:
                buf = self._to_dev(
                    self._pad(col, self._bucket(n), INT64_MAX))
            hist, dhist, distinct = jt["sketch_hist"](
                buf, n, buckets=SKETCH_BUCKETS)
            out = {"n": n, "distinct": int(self._to_host(distinct)),
                   "hist": self._to_host(hist).astype(np.int64),
                   "dhist": self._to_host(dhist).astype(np.int64)}
            if use_cache:
                self.cache.put(("sketch", cache_key), version, out,
                               out["hist"].nbytes + out["dhist"].nbytes)
        return out
