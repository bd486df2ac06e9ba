#!/usr/bin/env python3
"""Sound readings and controls of the comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 7,8,9 --seconds 5

In one process, so that programs compile once: a run of the cell at
each of ``--seeds``, then one with the loop's ``CONTROL`` switched on at
each of ``--control-seeds``.  A control is the program's own path that
breaks a guarantee the configuration states: the closure and streaming
loops stop the fixpoint after two rounds, the serving loop appends
without re-inferring.  Prints one JSON line per run, then a summary:
the largest reading of each check over the sound runs (the lower
reading) and the smallest over the controls (the upper one).  Needs
the chip, as ``bench/run.py`` does; the benchmark's own runs never run
a control.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def readings(cell, seeds, control, seconds, devs, meter) -> list:
    out = []
    for seed in seeds:
        res = harness.run_cell(cell, seed, seconds, False,
                               time.perf_counter(), devs, meter,
                               control=control)
        line = {"seed": seed, "control": control,
                "correct": res["correct"], "metrics": res["metrics"],
                "checks": {k: c["value"] for k, c in res["checks"].items()}}
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()
    meter = harness.CompileMeter()
    meter.install()
    seeds = [int(s) for s in args.seeds.split(",")]
    cseeds = [int(s) for s in args.control_seeds.split(",")]
    sound = readings(cell, seeds, False, args.seconds, devs[:1], meter)
    ctl = readings(cell, cseeds, True, args.seconds, devs[:1], meter)
    names = sound[0]["checks"]
    print(json.dumps({"summary": {
        name: {"lower": max(r["checks"][name] for r in sound),
               "upper": min(r["checks"][name] for r in ctl)}
        for name in names},
        "sound_correct": sum(r["correct"] for r in sound),
        "sound_runs": len(sound),
        "control_incorrect": sum(not r["correct"] for r in ctl),
        "control_runs": len(ctl)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
