"""Cold-store point query: demand-driven evaluation end to end.

    PYTHONPATH=src python examples/point_query.py [--backend B]
                                                  [--chains K] [--hops L]
                                                  [--shards S]

The serving-shaped workload the demand transformation targets: a store
is loaded and recursive rules are registered, but nothing is inferred —
then a point query arrives.  Under ``eval_mode="full"`` the engine
would have to materialize the whole closure (every chain's paths)
before it can answer; under ``eval_mode="demand"`` the query constants
seed per-type demand frontiers, restriction propagates backward through
the producing rules, and only the *queried* chain's cone is evaluated:

* ``demand_cone_rows`` — facts materialized for the cone (O(L²) for one
  chain, independent of how many chains are resident);
* ``rows_considered`` — join input rows actually touched, a small
  fraction of the full closure's;
* the sketch planner (``sort_mode="sketch"``) orders the joins from
  device-computed cardinality sketches, re-planning on 4x drift
  (``replans``);
* a re-query at unchanged table versions is a query-cache hit — no
  evaluation, no transfers, one row copy.

Results are checksum-identical to full evaluation (asserted below by
running both).
"""

from __future__ import annotations

import argparse

from repro.core import EngineConfig, Fact, HiperfactEngine, Rule
from repro.core.conditions import AddAction, cond, term


def make_rules() -> list[Rule]:
    """Transitive closure: path = edge | edge . path."""
    return [
        Rule("base", (cond("edge", "?x", "to", "?y"),),
             (AddAction("path", term("?x"), "to", term("?y")),)),
        Rule("rec", (cond("edge", "?x", "to", "?y"),
                     cond("path", "?y", "to", "?z")),
             (AddAction("path", term("?x"), "to", term("?z")),)),
    ]


def make_facts(chains: int, hops: int) -> list[Fact]:
    """K disjoint chains of L edges; only chain 0 will be queried."""
    return [Fact("edge", f"c{k}_n{i}", "to", f"c{k}_n{i + 1}")
            for k in range(chains) for i in range(hops)]


def row_set(rows: list[dict]) -> set:
    return {tuple(sorted(r.items())) for r in rows}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="numpy",
                    choices=["numpy", "jax", "jax-pallas", "jax-interpret"])
    ap.add_argument("--chains", type=int, default=12)
    ap.add_argument("--hops", type=int, default=12)
    ap.add_argument("--shards", type=int, default=1)
    args = ap.parse_args()
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()

    import dataclasses
    query = [cond("path", "c0_n0", "to", "?z")]
    facts = make_facts(args.chains, args.hops)

    # -- demand engine: load + rules, NO infer() — the query drives it
    cfg = dataclasses.replace(EngineConfig.infer1(args.backend),
                              eval_mode="demand", sort_mode="sketch",
                              shards=args.shards)
    engine = HiperfactEngine(cfg)
    engine.add_rules(make_rules())
    engine.insert_facts(facts)
    rows = engine.query(query)
    st = engine.last_infer
    n = (engine.num_facts() if args.shards > 1
         else engine.store.num_facts())
    print(f"demand: {len(rows)} results from a cold store of "
          f"{len(facts)} edges ({args.chains} chains)")
    print(f"  cone_rows={st.demand_cone_rows} rounds={st.demand_rounds} "
          f"rows_considered={st.rows_considered} "
          f"fallbacks={st.demand_fallbacks} "
          f"sketch={st.sketch_hits}h/{st.sketch_misses}m "
          f"replans={st.replans}")
    # only the queried chain's cone was materialized
    assert n < len(facts) + args.chains * args.hops * (args.hops + 1) // 2
    assert st.demand_fallbacks == 0 and st.demand_cone_rows > 0

    # -- re-query at fixed versions: pure cache hit
    hits0 = engine.last_infer.query_cache_hits
    rows_again = engine.query(query)
    assert engine.last_infer.query_cache_hits == hits0 + 1
    assert row_set(rows_again) == row_set(rows)
    print(f"  re-query: cache hit, {len(rows_again)} rows, no evaluation")

    # -- full-closure comparator: same answers, much more work
    full = HiperfactEngine(dataclasses.replace(cfg, eval_mode="full",
                                               sort_mode="sortkeys"))
    full.add_rules(make_rules())
    full.insert_facts(facts)
    fs = full.infer()
    full_rows = full.query(query)
    print(f"full: inferred {fs.facts_inferred} facts to answer the same "
          f"query (rows_considered={full.last_infer.rows_considered})")
    assert row_set(full_rows) == row_set(rows), "demand ≠ full!"
    ratio = st.rows_considered / max(full.last_infer.rows_considered, 1)
    print(f"parity OK; demand touched {100 * ratio:.1f}% of full's rows")


if __name__ == "__main__":
    main()
