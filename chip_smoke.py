#!/usr/bin/env python3
"""Smoke test of the fact engine's main path on TPU.

    python chip_smoke.py              # one chip: load, infer, serve, compare
    python chip_smoke.py --chips 4    # shards=4 on four chips vs numpy shards=1

One chip (the default).  Generates LUBM-shaped facts from ``--seed``
(``benchmarks.datasets.lubm_like``), loads them into a
``HiperfactEngine`` with ``EngineConfig.infer1("jax")`` and the RDFS-Plus
rules, runs ``infer()`` to the fixpoint on the chip, appends a few
hundred facts and infers again (the warm, incremental round), then
serves point probes and LUBM queries from reader threads through
``FactServer``.  Every answer is compared with references: the same
load on the numpy backend (fact counts, ``decoded_fact_checksum``,
served answers against its ``query``), and the classic Rete engine
(``core/rete_baseline``) on all four LUBM queries at a small scale.

Four chips.  A smaller LUBM load through ``EngineConfig(shards=4)``,
each shard worker on its own chip with the device frontier exchange,
against ``shards=1`` on the numpy twin; the checksums must be equal.

Each phase prints one JSON line.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU the script exits non-zero before running anything.  Times
printed here are smoke times, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# LUBM(50), the paper's largest LUBM, is about 6.8M triples; lubm_like
# makes about 2,136 facts per university, so scale 470 is just over 1M.
DEFAULT_SCALE = 470
# The sharded phase is smaller: each shard's device compiles its own copy
# of every program (the device is part of the executable), so four chips
# compile about three times as many programs as one.
SHARDED_SCALE = 24
PAPER_TRIPLES = 6_800_000
RETE_SCALE = 2


class SmokeFailure(AssertionError):
    """An answer on the device differs from its reference."""


def emit(phase: str, meter: "CompileMeter | None" = None, **fields) -> None:
    """One JSON line per phase; ``meter`` adds the compile totals so far
    (a run cut short still shows where its time went)."""
    if meter is not None:
        fields["compile_so_far"] = meter.stats()
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# building blocks (also driven at tiny size by tests/test_chip_smoke.py)


def smoke_config(backend: str, shards: int = 1):
    from repro.core import EngineConfig
    return dataclasses.replace(EngineConfig.infer1(backend),
                               eval_mode="delta", shards=shards)


def load_engine(backend: str, facts: list, shards: int = 1):
    """A fresh engine with the RDFS-Plus rules and ``facts`` loaded
    through the normal insert path.  Returns (engine, load seconds)."""
    from repro.core import HiperfactEngine
    from repro.core.rulesets import rdfs_plus_rules
    e = HiperfactEngine(smoke_config(backend, shards))
    e.add_rules(rdfs_plus_rules())
    t0 = time.perf_counter()
    e.insert_facts(facts)
    return e, time.perf_counter() - t0


def append_batch(n: int, seed: int) -> list:
    """About ``n`` new facts: graduate students joining an existing
    department, each with a type, a membership, an advisor and a course
    (their closure rows follow from the rules)."""
    import numpy as np
    from repro.core import Fact
    rng = np.random.RandomState(seed + 1)
    out = []
    for i in range(max(1, n // 4)):
        s = f"newstu{seed}_{i}"
        out += [Fact("Data", s, "type", "GraduateStudent"),
                Fact("Data", s, "memberOf", "dept0_0"),
                Fact("Data", s, "advisor", f"prof0_0_{rng.randint(6)}"),
                Fact("Data", s, "takesCourse",
                     f"course0_0_{rng.randint(6)}_{rng.randint(2)}")]
    return out


def fact_count(engine) -> int:
    """Alive facts in owner tables (shard view copies excluded)."""
    from repro.core.sharded import VIEW_PREFIX
    stores = ([w.store for w in engine.workers]
              if hasattr(engine, "workers") else [engine.store])
    return sum(int(tab.alive.sum()) for st in stores
               for name, tab in st.tables.items()
               if not name.startswith(VIEW_PREFIX))


def engine_ops(engine) -> list:
    return ([w.ops for w in engine.workers]
            if hasattr(engine, "workers") else [engine.ops])


def route_counts(engine) -> dict:
    """Kernel calls per route and host fallbacks, summed over shards."""
    out = {"pallas": 0, "xla": 0, "host": {}}
    for ops in engine_ops(engine):
        st = ops.route_stats()
        out["pallas"] += st["pallas"]
        out["xla"] += st["xla"]
        for k, v in st["host"].items():
            out["host"][k] = out["host"].get(k, 0) + v
    return out


def rows_key(rows) -> list:
    return sorted(tuple(sorted(r.items())) for r in rows)


def probe_queries(scale: int, n: int) -> list:
    """``n`` batched point probes ``(Data, <individual>, type, ?t)`` over
    students and professors spread across the universities."""
    from repro.core.conditions import cond
    out = []
    for i in range(n):
        u, d = (i * 7919) % max(1, scale), i % 8
        who = f"stu{u}_{d}_{i % 40}" if i % 3 else f"prof{u}_{d}_{i % 6}"
        out.append([cond("Data", who, "type", "?t")])
    return out


def serve_queries(engine, queries: list, readers: int) -> tuple:
    """Answer ``queries`` through a FactServer from ``readers`` threads.
    Returns (rows per query, serve stats, seconds)."""
    from repro.serve import FactServer
    with FactServer(engine) as srv:
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(readers) as pool:
            results = list(pool.map(srv.serve, queries))
        dt = time.perf_counter() - t0
        stats = srv.stats()
    return [r.rows for r in results], stats, dt


def device_peak_bytes(engine) -> list:
    out = []
    for dev in sorted({d for ops in engine_ops(engine)
                       for d in ops.resident_devices()},
                      key=lambda d: d.id):
        ms = dev.memory_stats() or {}
        out.append({"device": dev.id,
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use")})
    return out


def run_one_chip(scale: int, seed: int, backend: str = "jax",
                 append_n: int = 400, n_probes: int = 36,
                 readers: int = 2, rete_scale: int = RETE_SCALE,
                 meter: "CompileMeter | None" = None) -> dict:
    """Phases (b)-(e) on one device.  Raises ``SmokeFailure`` on any
    mismatch with a reference; returns the checked numbers."""
    from benchmarks.datasets import LUBM_QUERIES, lubm_like
    from repro.core.sharded import decoded_fact_checksum

    # (b) load
    t0 = time.perf_counter()
    facts = lubm_like(scale, seed=seed)
    gen_s = time.perf_counter() - t0
    eng, load_s = load_engine(backend, facts)
    emit("load", meter, backend=backend, scale=scale, seed=seed,
         base_facts=len(facts), generate_s=gen_s, load_s=load_s,
         cut=(f"lubm_like({scale}) = {len(facts)} facts, "
              f"{len(facts) / PAPER_TRIPLES:.3f} of LUBM(50)'s "
              f"~{PAPER_TRIPLES} triples"))

    # (c) infer: cold (compiles), then warm after a small append
    t0 = time.perf_counter()
    st = eng.infer()
    cold_s = time.perf_counter() - t0
    check(st.iterations < eng.config.max_iterations,
          "infer() stopped at max_iterations, not at the fixpoint")
    n_cold = fact_count(eng)
    extra = append_batch(append_n, seed)
    eng.insert_facts(extra)
    t0 = time.perf_counter()
    st2 = eng.infer()
    warm_s = time.perf_counter() - t0
    n_total = fact_count(eng)
    res = eng.ops.residency_stats()
    emit("infer", meter, iterations=st.iterations, fixpoint=True,
         base_facts=len(facts), derived_facts=n_cold - len(facts),
         first_infer_s=cold_s, appended=len(extra),
         warm_infer_s=warm_s, warm_iterations=st2.iterations,
         warm_derived=st2.facts_inferred,
         total_facts=n_total,
         resident_bytes_coded=res["resident_bytes_coded"],
         resident_bytes_raw=res["resident_bytes_raw"],
         device_memory=device_peak_bytes(eng))

    # (d) serve: batched point probes + single-condition LUBM queries
    queries = (probe_queries(scale, n_probes)
               + [LUBM_QUERIES[0], LUBM_QUERIES[2]] * 2)
    served, sstats, serve_s = serve_queries(eng, queries, readers)
    emit("serve", meter, queries=len(queries), readers=readers, serve_s=serve_s,
         served=sstats["served"], rows=sum(len(r) for r in served))

    # (e) compare with the numpy twin (same data, same append)
    ref, _ = load_engine("numpy", facts)
    ref.infer()
    check(fact_count(ref) == n_cold,
          f"fact count {n_cold} != numpy twin {fact_count(ref)}")
    ref.insert_facts(extra)
    ref.infer()
    check(fact_count(ref) == n_total,
          f"fact count after append {n_total} != numpy twin "
          f"{fact_count(ref)}")
    ck, ck_ref = decoded_fact_checksum(eng), decoded_fact_checksum(ref)
    check(ck == ck_ref, f"fact checksum {ck} != numpy twin {ck_ref}")
    bad = [i for i, (q, rows) in enumerate(zip(queries, served))
           if rows_key(rows) != rows_key(ref.query(q))]
    check(not bad, f"served answers differ from the numpy twin: {bad}")

    # independent reference: classic Rete at a small scale
    rete = rete_compare(backend, rete_scale, seed)
    emit("compare", meter, checksum=ck, numpy_checksum=ck_ref,
         facts=n_total, served_equal=len(queries), rete=rete,
         routes=route_counts(eng))
    return {"checksum": ck, "facts": n_total, "routes": route_counts(eng)}


def rete_compare(backend: str, scale: int, seed: int) -> dict:
    """All four LUBM queries on ``backend`` against ``ReteEngine``."""
    from benchmarks.datasets import LUBM_QUERIES, lubm_like
    from repro.core.rete_baseline import ReteEngine
    from repro.core.rulesets import rdfs_plus_rules
    facts = lubm_like(scale, seed=seed)
    eng, _ = load_engine(backend, facts)
    eng.infer()
    r = ReteEngine()
    for rule in rdfs_plus_rules():
        r.add_rule(rule)
    r.insert(facts)
    r.infer()
    rows = []
    for i, q in enumerate(LUBM_QUERIES):
        got, want = rows_key(eng.query(q)), rows_key(r.query(q))
        check(got == want, f"LUBM query {i}: {len(got)} rows on "
                           f"{backend}, {len(want)} on Rete")
        rows.append(len(got))
    return {"scale": scale, "facts": len(facts), "query_rows": rows}


def run_sharded(n_shards: int, scale: int, seed: int,
                backend: str = "jax",
                meter: "CompileMeter | None" = None) -> dict:
    """``shards=n_shards`` on ``backend`` (one device per shard) against
    ``shards=1`` on the numpy twin, on the same data; raises
    ``SmokeFailure`` unless the fact sets are identical, every worker's
    buffers sit on a device of its own, and the exchange ran on the
    devices.  The reference runs on the host: the one-chip phase holds
    the device's ``shards=1`` path to the same twin, and on four chips
    it would compile its programs once more."""
    from benchmarks.datasets import lubm_like
    from repro.core.sharded import decoded_fact_checksum
    facts = lubm_like(scale, seed=seed)
    out = {}
    for shards, be in ((n_shards, backend), (1, "numpy")):
        eng, load_s = load_engine(be, facts, shards=shards)
        t0 = time.perf_counter()
        st = eng.infer()
        cold_s = time.perf_counter() - t0
        check(st.iterations < eng.config.max_iterations,
              "infer() stopped at max_iterations, not at the fixpoint")
        ck = decoded_fact_checksum(eng)
        rec = {"shards": shards, "backend": be, "scale": scale,
               "base_facts": len(facts), "facts": fact_count(eng),
               "checksum": ck, "iterations": st.iterations,
               "load_s": load_s, "first_infer_s": cold_s}
        if shards > 1:
            devs = [sorted(d.id for d in w.ops.resident_devices())
                    for w in eng.workers]
            check(all(len(d) == 1 for d in devs)
                  and len({d[0] for d in devs}) == shards,
                  f"shard buffers are not on {shards} distinct devices: "
                  f"{devs}")
            check(eng.exchange.device, "frontier exchange ran on the host")
            rec["routes"] = route_counts(eng)
            rec["device_memory"] = device_peak_bytes(eng)
            rec["worker_devices"] = devs
            rec["exchange_device"] = eng.exchange.device
            rec["exchange_rounds"] = [
                {"phase": lg["phase"], "rows": lg["rows"],
                 "payload_bytes": lg["payload_bytes"],
                 "padded_bytes": lg["padded_bytes"],
                 "padded_bytes_wire": lg["padded_bytes_wire"]}
                for lg in eng.exchange_log if lg["rows"]]
        emit("sharded", meter, **rec)
        out[shards] = rec
    check(out[n_shards]["checksum"] == out[1]["checksum"],
          f"shards={n_shards} checksum {out[n_shards]['checksum']} != "
          f"shards=1 {out[1]['checksum']}")
    check(out[n_shards]["facts"] == out[1]["facts"], "fact counts differ")
    return out


# ---------------------------------------------------------------------------
# compile accounting


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's own monitoring events."""

    def __init__(self) -> None:
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self) -> None:
        from jax import monitoring

        def on_duration(event: str, secs: float, **_) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs
                self.compiles += 1

        def on_event(event: str, **_) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def stats(self) -> dict:
        return {"backend_compile_s": self.compile_s,
                "programs": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase, one shard per "
                         "chip, against shards=1")
    ap.add_argument("--scale", type=int, default=None,
                    help="lubm_like universities (~2,136 facts each); "
                         f"default {DEFAULT_SCALE}, or {SHARDED_SCALE} "
                         "with --chips 4")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}; "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 2

    for p in (os.path.join(REPO, "src"), REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    meter = CompileMeter()
    meter.install()
    emit("device", platform=devs[0].platform, kind=devs[0].device_kind,
         count=len(devs), compile_cache=cache_dir)

    t0 = time.perf_counter()
    if args.chips > 1:
        run_sharded(args.chips, args.scale or SHARDED_SCALE, args.seed,
                    meter=meter)
    else:
        run_one_chip(args.scale or DEFAULT_SCALE, args.seed, meter=meter)
    emit("compile", wall_s=time.perf_counter() - t0, **meter.stats())
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
