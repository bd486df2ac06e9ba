"""Streaming appends: load -> infer -> append -> re-infer, with stats.

    PYTHONPATH=src python examples/streaming_append.py [--backend B]
                                                       [--rounds N]

The serving-shaped workload the delta machinery targets: a knowledge
base is loaded and closed once, then small fact batches stream in and
`infer()` is called after each.  Three layers keep the per-round cost
proportional to the append (Δ), not the store (N) — each is printed per
round so the scaling is visible, not asserted:

* **semi-naive evaluation** (`eval_mode="auto"`): only rule passes whose
  append frontier is non-empty run, against O(Δ) tail scans
  (`delta_passes` vs `full_evals`, `rows_considered`);
* **delta-only uploads** (device backends): resident column buffers
  extend in place, so `h2d` bytes are delta buckets;
* **merge-maintained index mirrors** (device backends): the rank-1
  (sorted, perm) mirrors absorb each append by delta-run merge —
  `merged` bytes ∝ Δ — instead of full re-sorts (`sorted` bytes ∝ N).

Run with `--backend jax-interpret` to exercise the real device code path
on a CPU container (the CI smoke pass does); `numpy` shows the
host-side semi-naive stats only.
"""

from __future__ import annotations

import argparse

from repro.core import EngineConfig, Fact, HiperfactEngine, Rule
from repro.core.conditions import AddAction, cond, term


def make_rules() -> list[Rule]:
    return [
        Rule("subclass-trans",
             (cond("Schema", "?a", "subClassOf", "?b"),
              cond("Schema", "?b", "subClassOf", "?c")),
             (AddAction("Schema", term("?a"), "subClassOf", term("?c")),)),
        Rule("type-inherit",
             (cond("Data", "?x", "type", "?t"),
              cond("Schema", "?t", "subClassOf", "?u")),
             (AddAction("Data", term("?x"), "type", term("?u")),)),
        Rule("knows-symmetric",
             (cond("Data", "?x", "knows", "?y"),),
             (AddAction("Data", term("?y"), "knows", term("?x")),)),
    ]


def base_facts(n_classes: int = 12, n_entities: int = 400) -> list[Fact]:
    facts = [Fact("Schema", f"C{i}", "subClassOf", f"C{i + 1}")
             for i in range(n_classes - 1)]
    for e in range(n_entities):
        facts.append(Fact("Data", f"e{e}", "type", f"C{e % n_classes}"))
        if e:
            facts.append(Fact("Data", f"e{e}", "knows", f"e{e - 1}"))
    return facts


def append_batch(round_idx: int, batch: int = 25) -> list[Fact]:
    off = 10_000 + round_idx * batch
    return [Fact("Data", f"e{off + i}", "type", f"C{i % 3}")
            for i in range(batch)] + [
        Fact("Data", f"e{off + i}", "knows", f"e{off + i - 1}")
        for i in range(1, batch)]


def counters(ops):
    """(transfers, sort_work) snapshots, or (None, None) on host."""
    tc = getattr(ops, "transfers", None)
    sw = getattr(ops, "sort_work", None)
    return (tc.snapshot() if tc else None, sw.snapshot() if sw else None)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="numpy",
                    choices=["numpy", "jax", "jax-pallas", "jax-interpret"])
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--entities", type=int, default=400,
                    help="base dataset size (CI smoke uses a small one)")
    args = ap.parse_args()
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()

    import dataclasses
    # AI = the sorted-mirror index, rebuilt per append — the config whose
    # appends exercise merge maintenance (LPIM would batch them into an
    # unsorted tail and compact later)
    cfg = dataclasses.replace(EngineConfig.infer1(args.backend),
                              index_backend="AI")
    engine = HiperfactEngine(cfg)
    engine.add_rules(make_rules())

    # -- load + initial closure -------------------------------------------
    engine.insert_facts(base_facts(n_entities=args.entities))
    stats = engine.infer()
    print(f"load: {engine.store.num_facts()} facts, initial infer "
          f"{stats.seconds:.3f}s -> +{stats.facts_inferred} inferred "
          f"in {stats.iterations} rounds")

    # -- streaming appends ------------------------------------------------
    for r in range(args.rounds):
        tc0, sw0 = counters(engine.ops)
        engine.insert_facts(append_batch(r))
        stats = engine.infer()
        line = (f"round {r}: infer {stats.seconds:.3f}s "
                f"+{stats.facts_inferred} facts  "
                f"delta_passes={stats.delta_passes} "
                f"full_evals={stats.full_evals} "
                f"rows_considered={stats.rows_considered}")
        if tc0 is not None:
            d = engine.ops.transfers.delta(tc0)
            ds = engine.ops.sort_work.delta(sw0)
            line += (f"  h2d={d.h2d_bytes}B sorted={ds.sorted_bytes}B "
                     f"merged={ds.merged_bytes}B")
        print(line)

    # -- the re-infer at fixpoint is (nearly) free ------------------------
    tc0, _ = counters(engine.ops)
    stats = engine.infer()
    tail = ""
    if tc0 is not None:
        d = engine.ops.transfers.delta(tc0)
        tail = f" ({d.h2d_calls} h2d, {d.d2h_calls} d2h transfers)"
    print(f"fixpoint re-infer: {stats.seconds:.3f}s, "
          f"+{stats.facts_inferred} facts{tail}")

    n = engine.store.num_facts()
    got = engine.query([cond("Data", "?x", "type", "C11")])
    print(f"done: {n} facts total; {len(got)} entities reach type C11")
    assert stats.facts_inferred == 0  # fixpoint reached


if __name__ == "__main__":
    main()
