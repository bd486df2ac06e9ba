"""Divisibility-aware logical-axis -> mesh-axis sharding rules.

The production mesh is ``(data=16, model=16)`` single-pod or
``(pod=2, data=16, model=16)`` multi-pod (launch/mesh.py).  Logical rules:

    embed / batch      -> FSDP over (pod, data)     [ZeRO-3 via GSPMD]
    mlp / heads / kv /
    vocab / experts    -> TP / EP over model
    cache_seq          -> model (flash-decoding: sharded KV + LSE psum)
    layers             -> never sharded (scan dim)

Every mapping is checked for divisibility against the actual mesh — a dim
that does not divide falls back to replication (e.g. whisper's 51865
vocab), and a mesh axis is used at most once per tensor (first dim wins;
e.g. MoE weights [E, d, ff] keep E->model and drop ff->model).

Attention-activation policy: head-count TP when ``n_heads % model == 0``;
otherwise the attention core stays replicated over ``model`` (projections
remain TP) — recorded as a hillclimb lever in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.layers import Hints
from repro.models.params import LeafSpec, is_leaf_spec
import jax

# logical axis -> mesh axis group (tuples = composite FSDP axis)
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "embed": ("pod", "data"),
    "batch": ("pod", "data"),
    "mlp": ("model",),
    "heads": ("model",),
    "kv": ("model",),
    "heads3": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "cache_seq": ("model",),
    "layers": (),
}


def _present_axes(group: tuple[str, ...], mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in group if a in mesh.axis_names)


def _group_size(group: tuple[str, ...], mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in group], initial=1))


def spec_for(shape: tuple[int, ...], axes: tuple[str | None, ...],
             mesh: Mesh, rules: dict | None = None) -> P:
    """PartitionSpec for one tensor. Divisibility + axis-reuse checked."""
    rules = rules or DEFAULT_RULES
    used: set[str] = set()
    parts = []
    for dim, ax in zip(shape, axes):
        entry: tuple[str, ...] | None = None
        composite = False
        if ax is not None and ax in rules:
            # composite rules (FSDP over (pod, data)) keep tuple form even
            # when the mesh only has one of the axes, so specs compare
            # equal across single- and multi-pod meshes
            composite = len(rules[ax]) > 1
            group = _present_axes(rules[ax], mesh)
            if group and not (set(group) & used):
                size = _group_size(group, mesh)
                if size > 1 and dim % size == 0:
                    entry = group
                    used.update(group)
        parts.append(entry if entry is None or composite
                     else entry[0])
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def shardings_for(spec_tree, mesh: Mesh, rules: dict | None = None):
    """Pytree of NamedSharding matching a LeafSpec tree."""
    def one(s: LeafSpec):
        return NamedSharding(mesh, spec_for(s.shape, s.axes, mesh, rules))
    return jax.tree.map(one, spec_tree, is_leaf=is_leaf_spec)


def sharded_abstract(spec_tree, mesh: Mesh, rules: dict | None = None):
    """ShapeDtypeStruct tree with .sharding set (dry-run params stand-ins)."""
    def one(s: LeafSpec):
        return jax.ShapeDtypeStruct(
            s.shape, np.dtype(s.dtype),
            sharding=NamedSharding(mesh, spec_for(s.shape, s.axes, mesh,
                                                  rules)))
    return jax.tree.map(one, spec_tree, is_leaf=is_leaf_spec)


# ---------------------------------------------------------------------------
# Activation hints


def _dp(mesh: Mesh, batch: int) -> tuple[str, ...] | None:
    group = _present_axes(("pod", "data"), mesh)
    size = _group_size(group, mesh)
    if group and size > 1 and batch % size == 0:
        return group
    # try data alone (multi-pod with small batch)
    if "data" in mesh.axis_names and batch % mesh.shape["data"] == 0 \
            and mesh.shape["data"] > 1:
        return ("data",)
    return None


def activation_hints(cfg, mesh: Mesh, batch: int, kind: str = "train",
                     rules: dict | None = None) -> Hints:
    """Sharding constraints for the model's named activations.

    kind: train | prefill (sequence form) or decode (one-token form).
    """
    if mesh is None:
        return Hints()
    dp = _dp(mesh, batch)
    ms = mesh.shape.get("model", 1)
    head_tp = ms > 1 and cfg.q_heads() % ms == 0
    specs: dict[str, P] = {}
    if kind in ("train", "prefill"):
        sp = "model" if (cfg.seq_parallel and ms > 1) else None
        specs["residual"] = P(dp, sp, None)
        specs["attn_qflat"] = P(dp, None, "model")
        specs["attn_kvflat"] = P(dp, None, "model")
        if head_tp:
            specs["attn_q"] = P(dp, None, "model", None)
            specs["attn_out"] = P(dp, None, "model", None)
            if ms > 1 and cfg.n_kv_heads % ms == 0:
                specs["attn_kv"] = P(dp, None, "model", None)
            else:
                specs["attn_kv"] = P(dp, None, None, None)
        else:
            specs["attn_q"] = P(dp, None, None, None)
            specs["attn_kv"] = P(dp, None, None, None)
            specs["attn_out"] = P(dp, None, None, None)
        specs["mlp_hidden"] = P(dp, None, "model")
        specs["logits"] = P(dp, None, "model")
        specs["moe_buffer"] = P("model", None, None)
        specs["moe_hidden"] = P("model", None, None)
        specs["ssm_heads"] = P(dp, None, "model", None)
    else:  # decode: [B, 1, ...] activations
        specs["residual"] = P(dp, None, None)
        specs["attn_qflat"] = P(dp, None, "model")
        specs["attn_kvflat"] = P(dp, None, "model")
        specs["mlp_hidden"] = P(dp, None, "model")
        specs["moe_buffer"] = P("model", None, None)
        specs["moe_hidden"] = P("model", None, None)
    return Hints(specs=specs, mesh=mesh, kind=kind)


def batch_shardings(input_tree, mesh: Mesh, batch: int):
    """NamedShardings for a train/serve input batch: dim 0 = batch -> DP."""
    dp = _dp(mesh, batch)

    def one(x):
        nd = len(x.shape)
        return NamedSharding(mesh, P(dp, *([None] * (nd - 1))))
    return jax.tree.map(one, input_tree)


# ---------------------------------------------------------------------------
# Fact-table sharding (EngineConfig(shards=N))

FACT_AXIS = "shards"


def fact_mesh(n_shards: int, axis: str = FACT_AXIS) -> Mesh:
    """1-D device mesh for hash-partitioned fact tables.

    Each device owns the facts whose rank-1 key hashes to its index —
    the device-mesh generalization of the paper's derivation-tree
    parallel index writes (each writer owns a memory range).  Raises
    when the process has too few devices instead of silently folding
    into a degenerate mesh (CPU containers must set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before*
    jax initializes).  Shard ``i`` sits on ``jax.devices()[i]``, the
    device its worker's backend places its buffers on.
    """
    have = jax.device_count()
    if have < n_shards:
        raise ValueError(
            f"fact_mesh({n_shards}) needs {n_shards} devices but jax sees "
            f"{have}; set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_shards} before the first jax call")
    return Mesh(np.array(jax.devices()[:n_shards]), (axis,))


def fact_frontier_spec(axis: str = FACT_AXIS) -> P:
    """PartitionSpec of the packed per-shard frontier buffers: one send
    buffer row (``[n_shards * slot_cap]`` lanes) per mesh device."""
    return P(axis)
