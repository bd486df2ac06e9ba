"""Benchmark entry point: one section per paper table + the roofline.

    PYTHONPATH=src python -m benchmarks.run [--full] [--smoke]
                                            [--backend B] [--json PATH]

Default sizes are CPU-container friendly (~2-4 min); --full scales the
datasets up (the paper's LUBM50/100-class sizes); --smoke shrinks to
CI-sized inputs (inference presets + kernel micro only).

--json writes a machine-readable snapshot (op timings, transfer counts,
h2d bytes, cache stats) so the perf trajectory is tracked across PRs —
the convention is ``BENCH_<pr>.json`` at the repo root.
"""

from __future__ import annotations

import argparse
import json
import os
import time


def section(title: str):
    print(f"\n==== {title} " + "=" * max(0, 60 - len(title)), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized inputs: inference presets + kernel "
                         "micro only")
    ap.add_argument("--backend", default="numpy",
                    choices=["numpy", "jax", "jax-pallas", "jax-interpret"],
                    help="execution backend for the engine hot path "
                         "(see src/repro/backend/README.md)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a machine-readable snapshot "
                         "(BENCH_<pr>.json convention)")
    ap.add_argument("--eval-mode", default=None,
                    choices=["full", "delta", "auto", "demand"],
                    help="force the demand section on under --smoke "
                         "(demand) — non-smoke runs always include it")
    ap.add_argument("--serve", action="store_true",
                    help="force the serving section on under --smoke — "
                         "non-smoke runs always include it")
    ap.add_argument("--writers", type=int, default=2, metavar="N",
                    help="writer threads for the serving section")
    ap.add_argument("--readers", type=int, default=4, metavar="N",
                    help="reader threads for the serving section")
    ap.add_argument("--shards", type=int, default=0, metavar="N",
                    help="also bench the sharded fixpoint "
                         "(EngineConfig(shards=N) vs shards=1); forces "
                         "N host devices via XLA_FLAGS when no real "
                         "device mesh is configured")
    args = ap.parse_args()

    if args.shards > 1 and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        # must happen before the first jax import in this process
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.shards}").strip()

    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()

    t_start = time.perf_counter()
    report: dict = {"backend": args.backend, "smoke": args.smoke,
                    "full": args.full, "sections": {}}

    section(f"Table 2 analog: inference (backend={args.backend})")
    from benchmarks import bench_inference
    scale = 8 if args.full else 1
    # device backends: best-of-2 so one-time jit compilation doesn't
    # masquerade as steady-state cost in the snapshot
    runs = 2 if args.backend != "numpy" and not args.smoke else 1
    inf_rows = bench_inference.bench(scale=scale, backend=args.backend,
                                     smoke=args.smoke, runs=runs)
    report["sections"]["inference"] = [
        {"dataset": d, "engine": e, **r} for d, e, r in inf_rows]
    for dname, ename, r in inf_rows:
        print(f"{dname},{ename},load={r['load_s']:.4f}s,"
              f"infer={r['infer_s']:.4f}s,query={r['query_s']:.4f}s,"
              f"requery={r.get('requery_s', 0):.5f}s,"
              f"inferred={r['inferred']}")
        if "transfers" in r:
            sw = (" " + bench_inference.fmt_sort_work(r["sort_work"])
                  if "sort_work" in r else "")
            print(f"#   {ename}: "
                  f"{bench_inference.fmt_transfers(r['transfers'])}{sw} "
                  f"cache={r['cache']}")

    section(f"Streaming appends: semi-naive delta vs full "
            f"(backend={args.backend})")
    # --smoke keeps the delta path exercised on every CI push (small
    # scale, eval_mode=delta included) — see ISSUE 4 / backend README
    stream_scale = 2 if args.smoke else (16 if args.full else 8)
    stream_rows = bench_inference.bench_streaming(
        scale=stream_scale, backend=args.backend,
        n_rounds=2 if args.smoke else 4)
    report["sections"]["streaming"] = stream_rows
    by_mode = {r["mode"]: r for r in stream_rows}
    for r in stream_rows:
        per_round = ",".join(f"{x['infer_s']:.4f}s" for x in r["rounds"])
        xfer = ""
        if "h2d_bytes" in r["rounds"][0]:
            xfer = (" h2d=" + ",".join(str(x["h2d_bytes"])
                                       for x in r["rounds"]))
        if "merged_bytes" in r["rounds"][0]:
            # incremental index maintenance: merged (delta-run) vs full
            # re-sort bytes per append round
            xfer += (" sorted=" + ",".join(str(x["sorted_bytes"])
                                           for x in r["rounds"]) +
                     " merged=" + ",".join(str(x["merged_bytes"])
                                           for x in r["rounds"]))
        print(f"eval_mode={r['mode']},initial={r['initial_infer_s']:.4f}s,"
              f"reinfer=[{per_round}],facts={r['n_facts']},"
              f"checksum={r['checksum']}{xfer}")
        if "cache" in r:
            print(f"#   cache={r['cache']}")
    if {"full", "delta"} <= by_mode.keys():
        f, d = by_mode["full"], by_mode["delta"]
        ok = (f["checksum"] == d["checksum"]) and (f["n_facts"] == d["n_facts"])
        sp = f["reinfer_total_s"] / max(d["reinfer_total_s"], 1e-9)
        # steady state excludes the first round: a fresh engine's first
        # delta round pays one-time residency warm-up (uploads + index
        # mirrors), which a long-lived streaming engine never repeats
        steady_f = sum(x["infer_s"] for x in f["rounds"][1:])
        steady_d = sum(x["infer_s"] for x in d["rounds"][1:])
        sps = steady_f / max(steady_d, 1e-9)
        report["sections"]["streaming_summary"] = {
            "bit_identical": ok, "reinfer_speedup": sp,
            "steady_reinfer_speedup": sps}
        print(f"delta-vs-full: bit_identical={ok},reinfer_speedup={sp:.1f}x,"
              f"steady={sps:.1f}x")

    section(f"Streaming expiry: signed delta frontiers vs full "
            f"(backend={args.backend})")
    # append + bulk-expire rounds (IoT threshold rules): deletes must
    # ride O(Δ) negative passes — see ISSUE 7 / docs/ARCHITECTURE.md
    exp_shards = (1,) if args.smoke else (1, 4)
    exp_rows = bench_inference.bench_streaming_expire(
        backend=args.backend, shards_list=exp_shards,
        n_rounds=3 if args.smoke else 4,
        n_sensors=60 if args.smoke else 120,
        runs=1 if args.smoke else 2)
    exp_sum = bench_inference.summarize_streaming_expire(exp_rows)
    report["sections"]["streaming_expire"] = {
        "runs": exp_rows, **exp_sum}
    for r in exp_rows:
        per = ",".join(f"{x['append_infer_s'] + x['expire_infer_s']:.4f}s"
                       for x in r["rounds"])
        fe = ",".join(str(x["full_evals"]) for x in r["rounds"])
        neg = ",".join(str(x["neg_passes"]) for x in r["rounds"])
        print(f"eval_mode={r['mode']},shards={r['shards']},"
              f"rounds=[{per}],full_evals=[{fe}],neg_passes=[{neg}],"
              f"facts={r['n_facts']},checksum={r['checksum']}")
    exp_sp = {k: round(v, 1)
              for k, v in exp_sum["delta_vs_full_speedup"].items()}
    print(f"expire delta-vs-full: bit_identical={exp_sum['bit_identical']},"
          f"speedup={exp_sp},"
          f"steady_full_evals={exp_sum['steady_full_evals']}")

    if args.shards > 1:
        section(f"Sharded fixpoint: {args.shards}-way hash partition + "
                f"frontier all-to-all")
        sh = bench_inference.bench_sharded(
            shards=args.shards, scale=2 if args.full else 1,
            smoke=args.smoke)
        report["sections"]["sharded"] = sh
        for r in sh["runs"]:
            extra = ""
            if r["shards"] > 1:
                a2a = ",".join(str(x["a2a_payload_bytes"])
                               for x in r["append_rounds"])
                extra = (f",device={r['exchange_device']},"
                         f"critical_path={r['critical_path_s']:.4f}s,"
                         f"max_shard_b={max(r['shard_bytes'])},"
                         f"append_a2a_b=[{a2a}]")
            print(f"shards={r['shards']},load={r['load_s']:.4f}s,"
                  f"infer={r['infer_s']:.4f}s,facts={r['n_facts']},"
                  f"checksum={r['checksum']}{extra}")
        print(f"bit_identical={sh['bit_identical']},"
              f"max_shard_fraction={sh['max_shard_fraction']:.3f},"
              f"append_a2a_bytes={sh['append_a2a_bytes']},"
              f"resident_payload_bytes={sh['resident_payload_bytes']},"
              f"a2a_bytes_raw={sh.get('a2a_bytes_raw', 0)},"
              f"a2a_bytes_wire={sh.get('a2a_bytes_wire', 0)}")

    if not args.smoke or args.eval_mode == "demand":
        section(f"Demand-driven evaluation: cold-store point query "
                f"(backend={args.backend})")
        # magic-set cone vs full closure — see ISSUE 9 /
        # docs/ARCHITECTURE.md §Demand-driven evaluation
        dem = bench_inference.bench_demand(
            backend=args.backend, smoke=args.smoke,
            shards=max(1, args.shards))
        report["sections"]["demand"] = dem
        f, d = dem["full"], dem["demand"]
        print(f"full,query={f['query_s']:.4f}s,"
              f"rows_considered={f['rows_considered']},"
              f"inferred={f['inferred']},rows={f['rows']}")
        print(f"demand,query={d['query_s']:.4f}s,"
              f"rows_considered={d['rows_considered']},"
              f"cone_rows={d['cone_rows']},rounds={d['rounds']},"
              f"sketch={d['sketch_hits']}h/{d['sketch_misses']}m,"
              f"replans={d['replans']},rows={d['rows']}")
        rq = dem["requery"]
        xfer = (f",transfer_bytes={rq['transfer_bytes']}"
                if "transfer_bytes" in rq else "")
        print(f"requery,per_query={rq['per_query_s'] * 1e6:.1f}us"
              f"{xfer}")
        print(f"bit_identical={dem['bit_identical']},"
              f"rows_considered_ratio={dem['rows_considered_ratio']:.3f}")

    if not args.smoke or args.serve:
        section(f"Fact serving: concurrent writers + snapshot-isolated "
                f"readers (backend={args.backend})")
        # FactServer QPS + oracle parity — see ISSUE 10 /
        # docs/ARCHITECTURE.md §Serving tier
        sv = bench_inference.bench_serving(
            backend=args.backend, smoke=args.smoke,
            shards=max(1, args.shards), writers=args.writers,
            readers=args.readers)
        report["sections"]["serving"] = sv
        m = sv["mixed"]
        print(f"mixed,writers={m['writers']},readers={m['readers']},"
              f"ops={m['ops']},qps={m['qps']:.1f},"
              f"p50={m['p50_ms']:.2f}ms,p99={m['p99_ms']:.2f}ms,"
              f"checksum_ok={m['checksum_ok']},"
              f"torn_reads={m['torn_reads']}")
        rq = sv["requery"]
        print(f"requery,rounds={rq['rounds']},"
              f"full_evals={rq['full_evals']},"
              f"delta_folds={rq['delta_folds']},"
              f"p50={rq['p50_ms']:.2f}ms,p99={rq['p99_ms']:.2f}ms")
        b = sv["batching"]
        print(f"batching,device_calls={b['device_calls']},"
              f"batched_queries={b['batched_queries']},"
              f"coalesce_p50={b['coalesce_p50']:.1f},"
              f"coalesce_mean={b['coalesce_mean']:.2f}")

    if not args.smoke:
        section(f"Table 4 analog: query config matrix "
                f"(backend={args.backend})")
        from benchmarks import bench_query
        kw = {} if not args.full else {
            "mondial_kw": {"n_countries": 60, "cities_per": 120},
            "dblp_kw": {"n_papers": 20000, "n_authors": 3000}}
        q_rows = bench_query.bench(backend=args.backend, **kw)
        report["sections"]["query"] = [
            {"dataset": d, "config": c, **r} for d, c, r in q_rows]
        for dname, label, r in q_rows:
            print(f"{dname},{label},load={r['load_s']:.4f}s,"
                  f"query={r['query_s']:.6f}s")

        section("Hiperfact vs Rete scaling")
        from benchmarks import bench_vs_rete
        rete_rows = []
        for s, hf, rete in bench_vs_rete.bench(
                scales=(1, 2, 4) if not args.full else (1, 4, 8)):
            sp = rete["infer_s"] / max(hf["infer_s"], 1e-9)
            rete_rows.append({"scale": s, "facts": hf["n_facts"],
                              "hiperfact_s": hf["infer_s"],
                              "rete_s": rete["infer_s"], "speedup": sp})
            print(f"scale={s},facts={hf['n_facts']},"
                  f"hiperfact={hf['infer_s']:.4f}s,"
                  f"rete={rete['infer_s']:.4f}s,speedup={sp:.1f}x")
        report["sections"]["vs_rete"] = rete_rows

        section("Island processing internals (AR/DR, sort keys, order)")
        from benchmarks import bench_islands
        isl = []
        for label, dt, n in bench_islands.bench_rnl_modes():
            isl.append({"label": label, "seconds": dt, "rows": n})
            print(f"{label},{dt:.5f}s,rows={n}")
        for label, dt in bench_islands.bench_island_order():
            isl.append({"label": label, "seconds": dt})
            print(f"{label},{dt:.5f}s")
        report["sections"]["islands"] = isl

    section("Fork-join kernel micro (portable XLA paths)")
    from benchmarks import bench_kernels
    kn = (1 << 12) if args.smoke else (1 << 16)
    bn = (1 << 11) if args.smoke else (1 << 15)
    ops_rows = list(bench_kernels.bench(n=kn))
    ops_rows += bench_kernels.bench_backends(
        n=bn, names=("numpy", args.backend if args.backend != "numpy"
                     else "jax"))
    if not args.smoke:
        ops_rows += bench_kernels.bench_residency()
        ops_rows += bench_kernels.bench_batch_probe(
            backend=args.backend if args.backend != "numpy" else "jax")
    report["sections"]["kernels"] = [
        {"op": name, "value": v} for name, v in ops_rows]
    for name, s in ops_rows:
        print(f"{name},{s:.5f}s" if isinstance(s, float) else
              f"{name},{s}")

    section("Compressed resident columns (dict/FoR/RLE, lubm-like)")
    comp = bench_kernels.bench_compression(
        n=(1 << 13) if args.smoke else (1 << 15))
    report["sections"]["compression"] = comp
    for r in comp["runs"]:
        print(f"compression[{r['label']}],"
              f"resident_bytes_coded={r['resident_bytes_coded']},"
              f"checksum={r['checksum']},"
              f"codecs=for:{r['codecs']['for']}/dict:{r['codecs']['dict']}"
              f"/rle:{r['codecs']['rle']}")
    print(f"bit_identical={comp['bit_identical']},"
          f"bytes_per_fact={comp['bytes_per_fact_raw']:.2f}->"
          f"{comp['bytes_per_fact_coded']:.2f},"
          f"ratio={comp['ratio']:.2f}x")

    if not args.smoke:
        section("Extensions (paper §5): rank-N query cache + compression")
        from benchmarks import bench_extensions
        ext = []
        for label, dt, hr in bench_extensions.bench_query_cache():
            ext.append({"bench": "query-cache", "label": label,
                        "seconds": dt, "hit_rate": hr})
            print(f"query-cache,{label},{dt:.5f}s,hit_rate={hr:.2f}")
        for name, codec, ratio, enc_s in bench_extensions.bench_compression():
            ext.append({"bench": "compression", "name": name,
                        "codec": codec, "ratio": ratio, "seconds": enc_s})
            print(f"compression,{name},{codec},{ratio:.1f}x,{enc_s:.4f}s")
        report["sections"]["extensions"] = ext

        section("Roofline (from dry-run artifacts, if present)")
        from benchmarks import roofline
        for d in ("out/dryrun/single", "out/dryrun/multi"):
            if os.path.isdir(d) and os.listdir(d):
                print(f"-- {d}")
                rows = roofline.report(roofline.load(d))
                for r in rows:
                    print(f"{r['cell']},bound={r['bottleneck']},"
                          f"compute={r['compute_s']:.4f}s,"
                          f"memory={r['memory_s']:.4f}s,"
                          f"collective={r['collective_s']:.4f}s,"
                          f"useful={100*r['useful_ratio']:.1f}%,"
                          f"roofline={100*r['roofline_frac']:.2f}%")
            else:
                print(f"-- {d}: no artifacts (run repro.launch.dryrun "
                      f"first)")

    report["wall_seconds"] = time.perf_counter() - t_start
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1, default=float)
        print(f"\nwrote {args.json}")
    print(f"\nall benches done in {report['wall_seconds']:.1f}s")


if __name__ == "__main__":
    main()
