#!/usr/bin/env python3
"""Rate sweep of a serving cell, to find its knee.

    python3 bench/sweep.py --workload lubm-serve --seed 1 \
        --rates 10,20,40,80 --seconds 15

One process: set-up once, then one measured window per rate, in the
order given, on the same server.  For each rate it prints the read
percentiles, the 95th percentile of the window's first and second
halves, and how late the generator ran.  A backlog that grows through
the window shows as a second half far slower than the first.  The
knee is the highest rate without one; the cell's mix runs at about
four fifths of it.  Needs the chip, as ``bench/run.py`` does.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()
    meter = harness.CompileMeter()
    meter.install()
    run = harness.Run(cell, args.seed, harness.Tracer(False, cell.name))
    loop = harness.loop_module(cell.traffic).Loop(run)
    loop.setup()
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic["rate_per_s"] = rate
        loop.records = []
        t0 = time.perf_counter()
        before = meter.stats()["programs"]
        res = loop.window(args.seconds)
        c = res["counters"]
        lat = [1000.0 * (r["done"] - r["due"]) for r in loop.records
               if r["kind"] != "write" and "error" not in r]
        print(json.dumps({
            "rate_per_s": rate, "wall_s": time.perf_counter() - t0,
            "reads": c["reads"], "writes": c["writes"],
            "failed": res["failed"],
            "read_p50_ms": float(np.percentile(lat, 50)),
            "read_p95_ms": float(np.percentile(lat, 95)),
            "read_p99_ms": float(np.percentile(lat, 99)),
            "p95_first_half_ms": c["read_p95_first_half_ms"],
            "p95_second_half_ms": c["read_p95_second_half_ms"],
            "late_p95_ms": float(np.percentile(c["late_ms"], 95)),
            "compiled_in_window": meter.stats()["programs"] - before,
            "served": c["served"]}), flush=True)
    loop.server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
