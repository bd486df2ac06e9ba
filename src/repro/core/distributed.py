"""Distributed fact processing — the paper's engine at pod scale.

The paper confines itself to one node ("realizing the fact storage in a ...
distributed fashion is not part of this work"); this module is the natural
1000-chip extension of its two parallel ideas:

* derivation-tree **parallel index writes** (each thread owns a memory
  range) -> each device owns a hash partition of the fact space;
* the **fork-join sort-merge** instances -> fork = shard over the mesh,
  local work = the same sorted-array algebra, join = `all_to_all`
  repartitioning by join key (exactly a distributed sort-merge join).

Everything is fixed-capacity and fully jittable: relations are
sentinel-padded sorted buffers + counts, so one semi-naive fixpoint
iteration (``closure_step``) lowers/compiles on the production mesh —
this is the ``hiperfact_infer`` entry in the multi-pod dry-run.

The flagship workload is transitive closure (RDFS-Plus ``prp-trp`` /
``scm-sco`` — the recursive heart of the paper's LUBM benchmark).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.mergejoin.ops import expand_runs
from repro.kernels.prefix import prefix_sum

SENTINEL = jnp.iinfo(jnp.int64).max


def pack_pair(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Pack two int32 columns into one sortable int64 key."""
    return (a.astype(jnp.int64) << 32) | (b.astype(jnp.int64) & 0xFFFFFFFF)


def unpack_pair(p: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    return (p >> 32).astype(jnp.int32), (p & 0xFFFFFFFF).astype(jnp.int32)


def _mix64(z: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 on int64 lanes (device twin of store.splitmix64)."""
    z = z.astype(jnp.uint64) + jnp.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return (z ^ (z >> jnp.uint64(31))).astype(jnp.int64)


def _owner(keys: jnp.ndarray, n_dev: int) -> jnp.ndarray:
    """Owner shard per key — device twin of ``core.sharded.shard_of``.
    The modulo runs in uint64 so signed lanes agree with the host twin
    for any device count, not just powers of two."""
    h = _mix64(keys.astype(jnp.int64)).astype(jnp.uint64)
    return (h % jnp.uint64(n_dev)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# In-shard primitives (static shapes, sentinel padded)


def bucket_scatter(dest: jnp.ndarray, payload: jnp.ndarray, n_dev: int,
                   slot_cap: int, valid: jnp.ndarray,
                   sentinel: int = SENTINEL) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter ``payload`` rows into a ``[n_dev * slot_cap]`` send buffer by
    destination device.  Returns (buffer, overflow_count).  Out-of-capacity
    rows are dropped and counted (the host loop re-runs with a bigger slot
    cap if the overflow flag trips — bounded-buffer discipline).

    ``sentinel`` fills empty slots; compressed-wire lanes (sub-int64
    payload dtypes) pass their own dtype's max, since the int64 default
    does not fit."""
    d = jnp.where(valid, dest, n_dev)
    # rank of each row among the rows bound for its device, in input
    # order (one prefix sum per destination; no sort)
    idx_in_bucket = jnp.zeros(d.shape, jnp.int64)
    for k in range(n_dev):
        hit = d == k
        idx_in_bucket = jnp.where(hit, prefix_sum(hit) - 1, idx_in_bucket)
    ok = (d < n_dev) & (idx_in_bucket < slot_cap)
    pos = jnp.where(ok, d * slot_cap + idx_in_bucket, n_dev * slot_cap)
    buf = jnp.full((n_dev * slot_cap,), sentinel, dtype=payload.dtype)
    buf = buf.at[pos].set(payload, mode="drop")
    overflow = jnp.sum((d < n_dev) & (idx_in_bucket >= slot_cap))
    return buf, overflow


def join_expand_bounded(
    l_key: jnp.ndarray, l_payload: jnp.ndarray,
    r_sorted_key: jnp.ndarray, r_payload: jnp.ndarray,
    out_cap: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sorted equi-join with bounded emission.

    ``l_key`` (sentinel-padded) probes ``r_sorted_key`` (sorted, padded);
    emits up to ``out_cap`` (l_payload, r_payload) pairs + overflow count.
    The expansion is the merge join's (``mergejoin.ops.expand_runs``):
    pure index arithmetic, no data-dependent shapes.
    """
    l_valid = l_key != SENTINEL
    lo = jnp.searchsorted(r_sorted_key, l_key, side="left")
    hi = jnp.searchsorted(r_sorted_key, l_key, side="right")
    row, pos, ok, total = expand_runs(lo, jnp.where(l_valid, hi - lo, 0),
                                      out_cap)
    r_idx = jnp.clip(pos, 0, r_sorted_key.shape[0] - 1)
    out_l = jnp.where(ok, l_payload[row], SENTINEL)
    out_r = jnp.where(ok, r_payload[r_idx], SENTINEL)
    overflow = jnp.maximum(total - out_cap, 0)
    return out_l, out_r, overflow


def merge_sorted(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Merge two sorted arrays by rank arithmetic (O(n) traffic instead of
    an O(n log n) re-sort — the paper's SU *merge* pass, device form).

    Tie-break: 'left' on a vs 'right' on b makes target ranks disjoint.
    """
    na, nb = a.shape[0], b.shape[0]
    pos_a = jnp.arange(na) + jnp.searchsorted(b, a, side="left")
    pos_b = jnp.arange(nb) + jnp.searchsorted(a, b, side="right")
    out = jnp.zeros((na + nb,), a.dtype)
    return out.at[pos_a].set(a).at[pos_b].set(b)


def compact_masked(values_sorted: jnp.ndarray, mask: jnp.ndarray, cap: int,
                   fill) -> jnp.ndarray:
    """Keep masked entries of a sorted array, left-packed to ``cap`` —
    a cumsum scatter instead of a sort (§Perf: closure iteration 2)."""
    pos = jnp.where(mask, prefix_sum(mask) - 1, cap)
    out = jnp.full((cap,), fill, values_sorted.dtype)
    return out.at[pos].set(values_sorted, mode="drop")


def merge_unique(store_sorted: jnp.ndarray, new_keys: jnp.ndarray
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """SU unique filter + merge (paper §2.4 deduplication, device form).

    Returns (merged_sorted_store, fresh_keys (padded), n_fresh).  ``fresh``
    are new keys neither duplicated in the batch nor present in the store.
    Overflowing the store capacity drops the largest keys (flagged by the
    caller via count checks).

    §Perf (EXPERIMENTS.md): the store update is a rank-arithmetic *merge*
    of two sorted runs, not a re-sort of the whole store — only the small
    arrival buffer is ever sorted.
    """
    ns = jnp.sort(new_keys)
    first = jnp.concatenate([jnp.ones((1,), bool), ns[1:] != ns[:-1]])
    valid = (ns != SENTINEL) & first
    pos = jnp.clip(jnp.searchsorted(store_sorted, ns), 0,
                   store_sorted.shape[0] - 1)
    present = store_sorted[pos] == ns
    fresh_mask = valid & ~present
    fresh = compact_masked(ns, fresh_mask, ns.shape[0], SENTINEL)
    merged = merge_sorted(store_sorted, fresh)[: store_sorted.shape[0]]
    return merged, fresh, jnp.sum(fresh_mask)


# ---------------------------------------------------------------------------
# Distributed transitive closure (semi-naive)


@dataclasses.dataclass
class ClosureConfig:
    edge_cap: int = 1 << 14      # per-device closure/edge buffer capacity
    delta_cap: int = 1 << 12     # per-device frontier capacity
    slot_cap: int = 1 << 8       # per-destination all_to_all slots
    join_cap: int = 1 << 13      # per-device join emission capacity


def _device_index(axis_names: Sequence[str]) -> jnp.ndarray:
    idx = jnp.int32(0)
    for name in axis_names:
        idx = idx * jax.lax.axis_size(name) + jax.lax.axis_index(name)
    return idx


def _exchange(buf: jnp.ndarray, axis_names: Sequence[str], n_dev: int,
              slot_cap: int) -> jnp.ndarray:
    """all_to_all a [n_dev*slot_cap] send buffer -> received rows."""
    x = buf.reshape(n_dev, slot_cap)
    names = tuple(axis_names)
    x = jax.lax.all_to_all(x, names, split_axis=0, concat_axis=0, tiled=True)
    return x.reshape(n_dev * slot_cap)


def closure_step(state: dict, cfg: ClosureConfig, axis_names: Sequence[str],
                 n_dev: int) -> dict:
    """One semi-naive iteration, per shard (runs inside shard_map):

    Δ'(x,z) = Δ(x,y) ⋈ E(y,z), deduplicated against the closure store.
    Two all_to_all repartitions: Δ by join key y, results by owner hash(x).
    """
    # NOTE: inside shard_map each state leaf is the per-device shard:
    # edges/closure: [E] packed (src,dst) sorted; delta: [Δ] packed (x,y).
    edges = state["edges"]
    closure = state["closure"]
    delta = state["delta"]

    # 1. route Δ to the owner of its join key y
    _, y = unpack_pair(delta)
    dest = _owner(y, n_dev)
    valid = delta != SENTINEL
    buf, ovf1 = bucket_scatter(dest, delta, n_dev, cfg.slot_cap, valid)
    dj = _exchange(buf, axis_names, n_dev, cfg.slot_cap)

    # 2. local join on y: E is sorted by packed (src,dst) => prefix search by
    #    src works on the src-extracted (still sorted) view
    xj, yj = unpack_pair(dj)
    e_src = jnp.where(edges != SENTINEL, edges >> 32, SENTINEL >> 32)
    out_x, out_z_pair, ovf2 = join_expand_bounded(
        jnp.where(dj != SENTINEL, yj.astype(jnp.int64), SENTINEL),
        jnp.where(dj != SENTINEL, xj.astype(jnp.int64), SENTINEL),
        e_src, edges, cfg.join_cap)
    # out_x = x of delta, out_z_pair = packed (y,z) edge; build (x,z)
    _, z = unpack_pair(out_z_pair)
    new_pairs = jnp.where(out_x != SENTINEL,
                          pack_pair(out_x.astype(jnp.int32), z), SENTINEL)

    # 3. route new pairs to owner hash(x)
    nx, _ = unpack_pair(new_pairs)
    dest2 = _owner(nx, n_dev)
    buf2, ovf3 = bucket_scatter(dest2, new_pairs, n_dev, cfg.slot_cap,
                                new_pairs != SENTINEL)
    arrived = _exchange(buf2, axis_names, n_dev, cfg.slot_cap)

    # 4. dedup + merge into closure; fresh pairs become next Δ
    merged, fresh, n_fresh = merge_unique(closure, arrived)
    fresh_sorted = fresh[: cfg.delta_cap]  # already sorted + left-packed
    ovf4 = jnp.sum(fresh != SENTINEL) - jnp.sum(fresh_sorted != SENTINEL)
    # closure-store overflow: valid keys dropped by the capacity truncation
    ovf5 = (jnp.sum(closure != SENTINEL) + jnp.sum(fresh != SENTINEL)
            - jnp.sum(merged != SENTINEL))

    total_fresh = jax.lax.psum(n_fresh, tuple(axis_names))
    overflow = jax.lax.psum(ovf1 + ovf2 + ovf3 + ovf4 + ovf5,
                            tuple(axis_names))
    return {
        "edges": edges,
        "closure": merged,
        "delta": fresh_sorted,
        "fresh": jnp.asarray(total_fresh, jnp.int64)[None],
        "overflow": jnp.asarray(overflow, jnp.int64)[None],
    }


class DistributedClosure:
    """Host driver: partition edges, jit the shard_map step, loop to fixpoint."""

    def __init__(self, mesh: Mesh, cfg: ClosureConfig | None = None) -> None:
        self.mesh = mesh
        self.cfg = cfg or ClosureConfig()
        self.axis_names = tuple(mesh.axis_names)
        self.n_dev = int(np.prod(mesh.devices.shape))
        spec = P(self.axis_names)
        step = functools.partial(closure_step, cfg=self.cfg,
                                 axis_names=self.axis_names, n_dev=self.n_dev)
        self._step = jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=({k: spec for k in
                       ("edges", "closure", "delta", "fresh", "overflow")},),
            out_specs={k: spec for k in
                       ("edges", "closure", "delta", "fresh", "overflow")},
            check_vma=False))

    # -- state construction --------------------------------------------------
    def init_state(self, src: np.ndarray, dst: np.ndarray) -> dict:
        """Partition concrete edges: E shards by hash(src) (join side),
        closure/Δ shards by hash(x).

        Ownership uses the same ``shard_of`` as the engine's sharded mode
        (``core/sharded.py``), which is the host twin of the device
        ``_mix64`` used inside ``closure_step`` — the toy and the engine
        agree on which shard owns a key by construction.
        """
        from repro.core.sharded import shard_of

        cfg, D = self.cfg, self.n_dev
        packed = np.asarray(
            (src.astype(np.int64) << 32) | (dst.astype(np.int64) & 0xFFFFFFFF))
        h = shard_of(src.astype(np.int64), D)

        def shard_by(keys: np.ndarray, owners: np.ndarray, cap: int) -> np.ndarray:
            out = np.full((D, cap), np.iinfo(np.int64).max, np.int64)
            for d in range(D):
                mine = np.sort(keys[owners == d])[:cap]
                out[d, : len(mine)] = mine
            return out.reshape(D * cap)

        edges = shard_by(packed, h, cfg.edge_cap)
        closure = shard_by(packed, h, cfg.edge_cap)
        delta = shard_by(packed, h, cfg.delta_cap)
        sharding = NamedSharding(self.mesh, P(self.axis_names))
        return {
            "edges": jax.device_put(edges, sharding),
            "closure": jax.device_put(closure, sharding),
            "delta": jax.device_put(delta, sharding),
            "fresh": jax.device_put(np.zeros(D, np.int64), sharding),
            "overflow": jax.device_put(np.zeros(D, np.int64), sharding),
        }

    def run(self, src: np.ndarray, dst: np.ndarray, max_iters: int = 64
            ) -> tuple[np.ndarray, int]:
        """Compute full transitive closure; returns (packed pairs, iters)."""
        state = self.init_state(np.asarray(src, np.int64),
                                np.asarray(dst, np.int64))
        iters = 0
        for _ in range(max_iters):
            state = self._step(state)
            iters += 1
            if int(np.asarray(state["overflow"])[0]) > 0:
                raise RuntimeError(
                    "capacity overflow — raise ClosureConfig caps")
            if int(np.asarray(state["fresh"])[0]) == 0:
                break
        clo = np.asarray(state["closure"]).reshape(-1)
        return np.unique(clo[clo != np.iinfo(np.int64).max]), iters
