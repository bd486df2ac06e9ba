"""Island processing on a TPC-style OLAP schema (paper Fig. 7).

Shows the planner grouping a multi-star query into islands, ordering
them by estimated cost, and the effect on intermediate join sizes.

    PYTHONPATH=src python examples/olap_islands.py
"""

import time

import numpy as np

from repro.core import EngineConfig, Fact, HiperfactEngine, ValueType
from repro.core.conditions import Rule, cond
from repro.core.islands import build_islands, evaluate_rule, order_islands


def build_shop_kg(n_customers=1000, n_sales=4000, n_returns=400, seed=0):
    rng = np.random.RandomState(seed)
    facts = []
    for c in range(n_customers):
        facts.append(Fact("Customer", f"c{c}", "segment",
                          f"seg{rng.randint(5)}"))
    for s in range(n_sales):
        cid = f"c{rng.randint(n_customers)}"
        facts.append(Fact("StoreSale", f"s{s}", "customer", cid))
        facts.append(Fact("StoreSale", f"s{s}", "item",
                          f"i{rng.randint(200)}"))
        facts.append(Fact("StoreSale", f"s{s}", "amount",
                          int(rng.randint(1, 500)), ValueType.INT64))
    for r in range(n_returns):
        facts.append(Fact("StoreReturn", f"r{r}", "customer",
                          f"c{rng.randint(n_customers)}"))
        facts.append(Fact("StoreReturn", f"r{r}", "item",
                          f"i{rng.randint(200)}"))
    return facts


def main() -> None:
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()
    engine = HiperfactEngine(EngineConfig.query1())
    engine.insert_facts(build_shop_kg())

    # "customers in segment seg0 who returned an item they bought"
    query = (
        cond("Customer", "?c", "segment", "seg0"),
        cond("StoreSale", "?s", "customer", "?c"),
        cond("StoreSale", "?s", "item", "?i"),
        cond("StoreReturn", "?r", "customer", "?c"),
        cond("StoreReturn", "?r", "item", "?i"),
    )
    rule = Rule("returned-purchases", query)

    islands = build_islands(engine.store, rule)
    print("islands detected (paper Fig. 7 style):")
    for isl in order_islands(islands):
        conds = ", ".join(f"{s.cond.fact_type}(card={s.card:.0f})"
                          for s in isl.stats)
        print(f"  island ?{isl.key:3s} cost={isl.total_cost:9.0f}  [{conds}]")

    t0 = time.perf_counter()
    result = evaluate_rule(engine.store, rule, distinct=True)
    dt = time.perf_counter() - t0
    print(f"\nquery answered: {result.n} rows in {dt*1e3:.1f} ms")
    for i in range(min(5, result.n)):
        row = {k: int(result.col(k)[i]) for k in result.names()}
        print("  ", {k: engine.store.strings.lookup_id(v)
                     for k, v in row.items()})


if __name__ == "__main__":
    main()
