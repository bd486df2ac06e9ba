"""Counters the window loops read from the program, as deltas."""

from __future__ import annotations

INFER_FIELDS = ("iterations", "rows_considered", "rows_emitted",
                "delta_passes", "neg_passes", "full_evals",
                "facts_inferred", "facts_retracted")


def add_infer(acc: dict, st) -> None:
    """Sum an ``InferStats`` into ``acc``."""
    for f in INFER_FIELDS:
        acc[f] = acc.get(f, 0) + getattr(st, f)


def ops_snapshot(ops) -> dict:
    """The backend's own counters: device-cache lookups, kernel calls
    per route, host<->device bytes."""
    c = ops.cache.stats()
    r = ops.route_stats()
    return {"cache_hits": c["hits"], "cache_misses": c["misses"],
            "cache_stale": c["stale"], "cache_extended": c["extended"],
            "kernel_calls": r["pallas"] + r["xla"],
            "host_fallbacks": sum(r["host"].values()),
            "h2d_bytes": ops.transfers.h2d_bytes,
            "d2h_bytes": ops.transfers.d2h_bytes}


def ops_delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def add(acc: dict, d: dict) -> None:
    for k, v in d.items():
        acc[k] = acc.get(k, 0) + v
