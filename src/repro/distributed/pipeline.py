"""Inter-device pipelines: GPipe stages over ``pod`` and the sharded
fact engine's frontier all-to-all.

Pipeline parallelism (``pipeline_apply``): the multi-pod mesh has slow
inter-pod ICI; mapping pipeline *stages* to pods moves only
per-microbatch activations across the pod boundary instead of per-layer
FSDP all-gathers.  Implementation: layer-stacked params are sharded on
the ``layers`` dim over ``pod`` (each pod owns a contiguous stage), and
the step runs under ``shard_map`` with ``collective_permute`` handing
activations stage->stage while microbatches stream through (1F
schedule; the bubble is ``(stages-1)/microbatches``).

Frontier exchange (``FrontierExchange``): the transport of
``EngineConfig(shards=N)`` — each fixpoint round, every shard worker
hands over the *append frontier* rows whose derived keys hash to a
foreign shard.  Rows are packed into three int64 lanes (packed
``(id, attr)`` key / raw value / table-and-kind meta), bucketed per
destination with ``core.distributed.bucket_scatter``, and moved with
one ``lax.all_to_all`` under ``shard_map`` over a 1-D ``shards`` mesh
(``distributed.sharding.fact_mesh``).  Send-buffer capacity is exact:
the host knows every bucket count, so ``slot_cap`` is the
power-of-two-rounded max bucket — no overflow/retry loop, and the jit
cache only sees log-many ``(in_cap, slot_cap)`` shapes.  The numpy
backend runs the same exchange as a host permute with identical
semantics and byte accounting; a device backend with fewer devices than
shards is an error.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(block_fn, stacked_params, h: jnp.ndarray, *, mesh: Mesh,
                   n_stages: int, n_micro: int, axis: str = "pod"):
    """Run ``h`` through all layers with stage-sharded params.

    block_fn(layer_params, h_micro) -> h_micro.
    stacked_params leaves: [L_total, ...] sharded on dim 0 over ``axis``.
    h: [B, ...] with B % n_micro == 0.
    """
    B = h.shape[0]
    assert B % n_micro == 0
    mb = B // n_micro

    def stage_fn(params_stage, h_all):
        """Runs on one stage (inside shard_map). params_stage: [L/S, ...]."""
        sid = jax.lax.axis_index(axis)

        def run_stage(carry_h):
            def layer_body(hh, lp):
                return block_fn(lp, hh), None
            out, _ = jax.lax.scan(layer_body, carry_h, params_stage)
            return out

        # GPipe 1F schedule: n_micro + n_stages - 1 ticks.  Each tick: run
        # my stage on my current microbatch, then shift stage->stage+1.
        n_ticks = n_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        h_micro = h_all.reshape(n_micro, mb, *h_all.shape[1:])
        out_buf = jnp.zeros_like(h_micro)

        def tick(state, t):
            cur, out_buf = state
            # stage 0 injects microbatch t (if any) — others use received
            inject = jax.lax.dynamic_index_in_dim(
                h_micro, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            cur = jnp.where(sid == 0, inject, cur)
            processed = run_stage(cur)
            # last stage writes its finished microbatch t - (S-1)
            mb_done = t - (n_stages - 1)
            out_buf = jax.lax.cond(
                (sid == n_stages - 1) & (mb_done >= 0),
                lambda ob: jax.lax.dynamic_update_index_in_dim(
                    ob, processed, jnp.clip(mb_done, 0, n_micro - 1), 0),
                lambda ob: ob, out_buf)
            nxt = jax.lax.ppermute(processed, axis, perm)
            return (nxt, out_buf), None

        init = jnp.zeros((mb, *h_all.shape[1:]), h_all.dtype)
        (_, out_buf), _ = jax.lax.scan(
            tick, (init, out_buf), jnp.arange(n_ticks))
        # all stages exchanged: only the last stage holds real outputs;
        # broadcast them (masked psum) so every shard returns the same value
        out = out_buf.reshape(B, *h_all.shape[1:])
        out = jax.lax.psum(
            jnp.where(sid == n_stages - 1, out, jnp.zeros_like(out)), axis)
        return out

    fn = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(P(axis), P()),   # params stage-sharded; h replicated
        out_specs=P(),
        check_vma=False)
    return fn(stacked_params, h)


# ---------------------------------------------------------------------------
# Sharded-engine frontier exchange


def _pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


class FrontierExchange:
    """All-to-all transport for the sharded engine's append frontiers.

    ``exchange(dest, key, val, meta)`` takes per-source-shard host
    arrays (``dest``: int32 destination shard per row; the three int64
    payload lanes) and returns per-destination-shard received lanes
    plus a byte-accounting dict.  Row validity on the receive side is
    carried by the ``meta`` lane (small non-negative values; the
    sentinel never collides), so ``val`` may hold any int64 bit
    pattern.

    Device path: one jitted ``shard_map`` over ``fact_mesh(n_shards)``
    running ``bucket_scatter`` per lane + ``lax.all_to_all`` — the
    exact transport of ``core.distributed.closure_step``, generalized
    to arbitrary fact rows.  Host path (numpy backend only): the same
    permutation on host arrays.  A device backend with fewer devices
    than shards raises instead of falling back to the host.
    """

    def __init__(self, n_shards: int, prefer_device: bool = True,
                 compress: bool | None = None) -> None:
        self.n_shards = n_shards
        self.mesh = None
        self._fns: dict[tuple, object] = {}
        if compress is None:
            from repro.backend.codecs import compress_default
            compress = compress_default()
        self.compress = bool(compress)
        if prefer_device and n_shards > 1:
            # raises when the process has fewer devices than shards
            from repro.distributed.sharding import fact_mesh
            self.mesh = fact_mesh(n_shards)

    @property
    def device(self) -> bool:
        return self.mesh is not None

    # -- device path -------------------------------------------------------
    def _build(self, in_cap: int, slot_cap: int, sentinels: tuple):
        """Jitted per-(caps, wire dtypes) exchange step.  ``sentinels``
        are the per-lane empty-slot fills in wire domain — part of the
        cache key because the lane dtypes follow from them."""
        fn = self._fns.get((in_cap, slot_cap, sentinels))
        if fn is not None:
            return fn
        from repro.core.distributed import _exchange, bucket_scatter
        D = self.n_shards
        axis = self.mesh.axis_names[0]

        def step(dest, key, val, meta):
            d = dest.reshape(-1)
            valid = d >= 0
            out = []
            for lane, sent in zip((key, val, meta), sentinels):
                buf, _ovf = bucket_scatter(d, lane.reshape(-1), D, slot_cap,
                                           valid, sentinel=sent)
                out.append(_exchange(buf, (axis,), D, slot_cap)[None, :])
            return tuple(out)

        fn = jax.jit(jax.shard_map(
            step, mesh=self.mesh,
            in_specs=(P(axis),) * 4, out_specs=(P(axis),) * 3,
            check_vma=False))
        self._fns[(in_cap, slot_cap, sentinels)] = fn
        return fn

    def _lane_plans(self, key, val, meta):
        """Per-lane wire plans for one exchange round (``None`` entries
        ship raw int64).  The key and meta lanes narrow well (dense
        interned ids / small table-and-kind tags); the value lane may
        hold arbitrary bit patterns and usually stays raw."""
        from repro.distributed import compression as C
        if not self.compress:
            return (None, None, None)
        return tuple(C.lane_plan(list(lane)) for lane in (key, val, meta))

    def _exchange_device(self, dest, key, val, meta, slot_cap, plans):
        from repro.distributed import compression as C
        D = self.n_shards
        in_cap = _pow2(max(1, max(len(d) for d in dest)))
        dst = np.full((D, in_cap), -1, np.int32)
        lanes = [np.zeros((D, in_cap),
                          np.int64 if p is None else p[1])
                 for p in plans]
        for s in range(D):
            n = len(dest[s])
            dst[s, :n] = dest[s]
            for lane, col, p in zip(lanes, (key[s], val[s], meta[s]),
                                    plans):
                lane[s, :n] = C.narrow_lane(col, p)
        sentinels = tuple(C.lane_sentinel(p) for p in plans)
        fn = self._build(in_cap, slot_cap, sentinels)
        bk, bv, bm = (np.asarray(x) for x in fn(dst, *lanes))
        out = []
        for d in range(D):
            # row validity rides the meta lane: its wire sentinel marks
            # empty slots (real metas keep reserved headroom below it)
            ok = bm[d] != sentinels[2]
            out.append(tuple(C.widen_lane(b[d][ok], p)
                             for b, p in zip((bk, bv, bm), plans)))
        return out

    # -- host path ---------------------------------------------------------
    def _exchange_host(self, dest, key, val, meta):
        D = self.n_shards
        out = []
        for d in range(D):
            ks, vs, ms = [], [], []
            for s in range(D):
                m = dest[s] == d
                if m.any():
                    ks.append(key[s][m])
                    vs.append(val[s][m])
                    ms.append(meta[s][m])
            cat = lambda xs: (np.concatenate(xs) if xs
                              else np.empty(0, np.int64))
            out.append((cat(ks), cat(vs), cat(ms)))
        return out

    # -- public ------------------------------------------------------------
    def exchange(self, dest: list, key: list, val: list, meta: list
                 ) -> tuple[list, dict]:
        """Move rows to their destination shards.

        Returns ``([(key, val, meta)] * n_shards, stats)``.  Stats:
        ``payload_bytes`` (real rows x 24B — the Δ-proportional
        traffic), ``padded_bytes`` (what the bounded-buffer a2a
        actually moved), plus the compressed-wire mirror of each
        (``payload_bytes_wire`` / ``padded_bytes_wire``) when the
        per-round frame-of-reference lane narrowing is on — the wire
        keys equal the raw ones when every lane ships raw.
        """
        from repro.distributed import compression as C
        D = self.n_shards
        rows = int(sum(len(d) for d in dest))
        counts = np.zeros((D, D), np.int64)
        for s in range(D):
            if len(dest[s]):
                np.add.at(counts[s], dest[s], 1)
        slot_cap = _pow2(max(1, int(counts.max())))
        if rows == 0:
            empty = [(np.empty(0, np.int64),) * 3 for _ in range(D)]
            return empty, {"rows": 0, "payload_bytes": 0, "padded_bytes": 0,
                           "payload_bytes_wire": 0, "padded_bytes_wire": 0,
                           "slot_cap": 0, "device": self.device,
                           "compress": self.compress}
        plans = self._lane_plans(key, val, meta)
        row_wire = sum(C.wire_itemsize(p) for p in plans)
        if self.device:
            out = self._exchange_device(dest, key, val, meta, slot_cap,
                                        plans)
            padded = D * D * slot_cap * 3 * 8
            padded_wire = D * D * slot_cap * row_wire
        else:
            # host permute moves no wire bytes, but account what the
            # device transport *would* ship so numpy-backend runs report
            # comparable compression ratios
            out = self._exchange_host(dest, key, val, meta)
            padded = rows * 3 * 8
            padded_wire = rows * row_wire
        return out, {"rows": rows, "payload_bytes": rows * 3 * 8,
                     "padded_bytes": padded,
                     "payload_bytes_wire": rows * row_wire,
                     "padded_bytes_wire": padded_wire,
                     "slot_cap": slot_cap, "device": self.device,
                     "compress": self.compress}
