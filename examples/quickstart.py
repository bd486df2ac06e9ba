"""Quickstart: the Hiperfact engine in 60 lines.

    PYTHONPATH=src python examples/quickstart.py

Covers: facts (Def. 1), rules with computed actions (Def. 3), variable
join tests (Def. 9), inference to fixpoint, and ad-hoc queries.
"""

from repro.core import (EngineConfig, Fact, HiperfactEngine, Rule,
                        ValueType)
from repro.core.conditions import AddAction, cond, term


def main() -> None:
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()
    engine = HiperfactEngine(EngineConfig.infer1())

    # -- the paper's running example: derive USD profits ------------------
    engine.add_rule(Rule(
        "usd-profit",
        conditions=(
            cond("DailySales", "?s", "profitEUR", "?p", ValueType.DOUBLE),
            cond("DailySales", "?s", "EURUSD", "?f", ValueType.DOUBLE),
        ),
        actions=(AddAction(
            "DailySales", term("?s"), "profitUSD", None, ValueType.DOUBLE,
            compute=lambda b: _mul(b["p"], b["f"])),),
    ))
    # -- age classification with a join test (Def. 9) ---------------------
    engine.add_rule(Rule(
        "age-class",
        conditions=(
            cond("AgeClass", "?ac", "minAge", "?m", ValueType.UINT32),
            cond("Person", "?x", "age", "?a", ValueType.UINT32,
                 tests=[("?a", ">=", "?m")]),
        ),
        actions=(AddAction("Person", term("?x"), "inClass", term("?ac")),),
    ))

    engine.insert_facts([
        Fact("DailySales", "s1", "profitEUR", 100.0, ValueType.DOUBLE),
        Fact("DailySales", "s1", "EURUSD", 1.1, ValueType.DOUBLE),
        Fact("AgeClass", "kid", "minAge", 0, ValueType.UINT32),
        Fact("AgeClass", "adult", "minAge", 18, ValueType.UINT32),
        Fact("Person", "jane", "age", 30, ValueType.UINT32),
        Fact("Person", "tom", "age", 9, ValueType.UINT32),
    ])

    stats = engine.infer()
    print(f"inferred {stats.facts_inferred} facts in "
          f"{stats.iterations} fixpoint iterations "
          f"({stats.seconds*1e3:.1f} ms)")

    print("\nUSD profits:")
    for row in engine.query([cond("DailySales", "?s", "profitUSD", "?v",
                                  ValueType.DOUBLE)]):
        print(" ", row)

    print("\nage classes:")
    for row in engine.query([cond("Person", "?x", "inClass", "?c")]):
        print(" ", row)


def _mul(p, f):
    from repro.core.facts import decode_lane_array, encode_lane_array, \
        ValueType as VT
    import numpy as np
    return encode_lane_array(
        decode_lane_array(np.asarray(p), VT.DOUBLE)
        * decode_lane_array(np.asarray(f), VT.DOUBLE), VT.DOUBLE)


if __name__ == "__main__":
    main()
