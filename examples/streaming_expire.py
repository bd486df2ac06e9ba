"""Streaming expiry: append -> infer -> bulk-expire -> re-infer.

    PYTHONPATH=src python examples/streaming_expire.py [--backend B]
                                                       [--rounds N]
                                                       [--shards S]

The retraction-shaped workload the signed delta frontiers target: an
IoT fleet streams sensor readings in, a two-hop rule chain raises and
routes alerts, and every round the previous window of readings expires
wholesale (TTL).  Three layers keep the per-round cost proportional to
the *change* (Δ), not the store (N) — each is printed per round:

* **signed frontiers** (`eval_mode="delta"`, the default under "auto"):
  every `(rule, fact-type)` pass sees an O(Δ) window of +rows *and*
  -rows; deletions run negative inclusion–exclusion passes
  (`neg_passes`) over the delete log instead of re-evaluating the rule
  (`full_evals` stays 0 after warm-up);
* **counting support**: derived facts carry support counters, so a
  retraction only kills a fact whose last derivation died
  (`facts_retracted`), and deleting an asserted fact that is still
  derived elsewhere merely clears the assertion bit
  (`compensated_deletes`) — no churn, no index rebuilds;
* **bounded tombstones** (device backends): dead rows ride inside the
  sorted index mirrors until they exceed a quarter of the alive rows,
  so expiry does not trigger per-round mirror rebuilds.

Recursive rules are the one case counting cannot localize; those fall
back to a DRed overdelete/rederive scrub (`dred_scrubs`) — this
workload has none, so the counter stays 0.
"""

from __future__ import annotations

import argparse

from repro.core import EngineConfig, Fact, HiperfactEngine, Rule
from repro.core.conditions import AddAction, cond, term


def make_rules() -> list[Rule]:
    return [
        Rule("hot",
             (cond("Reading", "?s", "temp", "?t"),
              cond("Threshold", "?t", "class", "hot")),
             (AddAction("Alert", term("?s"), "level", "hot"),)),
        Rule("zone-alert",
             (cond("Alert", "?s", "level", "hot"),
              cond("Zone", "?s", "in", "?z")),
             (AddAction("ZoneAlert", term("?z"), "has", term("?s")),)),
        Rule("audit",
             (cond("ZoneAlert", "?z", "has", "?s"),),
             (AddAction("Audit", term("?z"), "saw", term("?s")),)),
    ]


def window(r: int, n_sensors: int) -> tuple[list[Fact], list[Fact]]:
    """One round's readings + zone memberships for a fresh sensor id
    range (sensor ids never repeat: this is a stream, not an update)."""
    base = r * n_sensors
    readings = [Fact("Reading", f"s{base + i}", "temp", f"t{i % 7}")
                for i in range(n_sensors)]
    zones = [Fact("Zone", f"s{base + i}", "in", f"z{i % 4}")
             for i in range(n_sensors)]
    return readings, zones


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="numpy",
                    choices=["numpy", "jax", "jax-pallas", "jax-interpret"])
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--sensors", type=int, default=200,
                    help="window size (CI smoke uses a small one)")
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--eval-mode", default="delta",
                    choices=["auto", "delta", "full"])
    args = ap.parse_args()
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()

    import dataclasses
    cfg = dataclasses.replace(EngineConfig.infer1(args.backend),
                              eval_mode=args.eval_mode, shards=args.shards)
    engine = HiperfactEngine(cfg)
    engine.add_rules(make_rules())
    engine.insert_facts([Fact("Threshold", f"t{k}", "class", "hot")
                         for k in (5, 6)])
    engine.infer()

    prev: list[Fact] | None = None
    for r in range(args.rounds):
        readings, zones = window(r, args.sensors)
        engine.insert_facts(readings + zones)
        sa = engine.infer()
        line = (f"round {r}: append infer {sa.seconds:.3f}s "
                f"+{sa.facts_inferred} facts "
                f"delta_passes={sa.delta_passes} "
                f"full_evals={sa.full_evals}")
        if prev is not None:
            engine.delete_facts(prev)
            sd = engine.infer()
            line += (f" | expire infer {sd.seconds:.3f}s "
                     f"-{sd.facts_retracted + sd.facts_deleted} facts "
                     f"neg_passes={sd.neg_passes} "
                     f"full_evals={sd.full_evals} "
                     f"compensated={sd.compensated_deletes} "
                     f"scrubs={sd.dred_scrubs}")
            if r > 1 and args.eval_mode != "full":
                # steady state: retraction is delta work, never a rescan
                assert sd.full_evals == 0, sd.full_evals
        prev = readings
        print(line)

    # only the newest window's alerts survive expiry
    n = (engine.num_facts() if args.shards > 1
         else engine.store.num_facts())
    alerts = engine.query([cond("Alert", "?s", "level", "hot")])
    hot_per_window = sum(1 for i in range(args.sensors) if i % 7 in (5, 6))
    print(f"done: {n} facts resident; {len(alerts)} live alerts "
          f"(one window's worth = {hot_per_window})")
    assert len(alerts) == hot_per_window


if __name__ == "__main__":
    main()
