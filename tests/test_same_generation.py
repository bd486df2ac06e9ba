"""OpenRuleBench same-generation (sg) through the engine's normal path.

The benchmark's ``orb-sg`` configuration (``bench/configs/orb-sg.json``)
runs through ``make_engine`` -> ``insert_facts`` -> ``infer()`` on
graphs of out-degree 5 from its generator, and its fact set is
compared with the benchmark's plain reference, with the Rete baseline,
and, for a base rule that carries an ``x != y`` join test, with a plain
set fixpoint filtered the same way.
"""

import dataclasses
import json
import os

import pytest

from bench import harness
from bench.reference import Reference
from repro.core import EngineConfig, HiperfactEngine
from repro.core.conditions import AddAction, Rule, cond, term
from repro.core.rete_baseline import ReteEngine

with open(os.path.join(harness.ROOT, "bench/configs/orb-sg.json")) as f:
    CONFIG = json.load(f)


def config(nodes, backend="numpy", eval_mode="delta"):
    cfg = json.loads(json.dumps(CONFIG))
    cfg["scale"] = {"nodes": nodes, "edges": 5 * nodes}
    cfg["engine"]["backend"] = backend
    cfg["engine"]["options"]["eval_mode"] = eval_mode
    return cfg


def sg_pairs(engine):
    return {(r["x"], r["y"])
            for r in engine.query([cond("sg", "?x", "to", "?y")])}


def plain_sg(edges, distinct_base):
    """Semi-naive set fixpoint of the two sg rules over ``(x, p)`` parent
    pairs; ``distinct_base`` drops ``x == y`` from the base rule."""
    children: dict = {}
    for x, p in edges:
        children.setdefault(p, []).append(x)
    sg = {(x, y) for kids in children.values() for x in kids for y in kids
          if not (distinct_base and x == y)}
    delta = set(sg)
    while delta:
        new = {(x, y) for a, b in delta for x in children.get(a, ())
               for y in children.get(b, ())} - sg
        sg |= new
        delta = new
    return sg


@pytest.mark.parametrize("eval_mode", ["delta", "full"])
@pytest.mark.parametrize("backend,nodes", [("numpy", 300),
                                           ("jax-interpret", 60)])
def test_sg_matches_the_reference(backend, nodes, eval_mode):
    cfg = config(nodes, backend, eval_mode)
    ds = harness.generator(cfg).generate(cfg, 7)
    engine = harness.make_engine(cfg)
    engine.insert_facts(ds.fact_objects())
    st = engine.infer()
    got, unknown = harness.engine_facts(engine, ds.vocab)
    ref = Reference(cfg["rules"], ds.vocab.id)
    ref.add(ds.facts)
    assert harness.compare_facts(got, unknown, ref.facts()) == {
        "missing_facts": 0, "extra_facts": 0, "duplicate_facts": 0}
    assert len(got["sg"]) > 5 * len(ds.facts["edge"])
    assert st.iterations >= 3


def test_sg_matches_rete():
    cfg = config(30)
    ds = harness.generator(cfg).generate(cfg, 11)
    facts = ds.fact_objects()
    engine = harness.make_engine(cfg)
    engine.insert_facts(facts)
    engine.infer()
    rete = ReteEngine()
    for rule in engine.rules:
        rete.add_rule(rule)
    rete.insert(facts)
    rete.infer()
    want = {(m["x"], m["y"])
            for m in rete.query([cond("sg", "?x", "to", "?y")])}
    assert sg_pairs(engine) == want and len(want) > 50


@pytest.mark.parametrize("backend", ["numpy", "jax-interpret"])
def test_sg_with_an_inequality_test(backend):
    """OpenRuleBench's base rule as written, with ``x != y`` as a join
    test (Def. 9) on the second condition."""
    cfg = config(60, backend)
    ds = harness.generator(cfg).generate(cfg, 13)
    facts = ds.fact_objects()
    head = (AddAction("sg", term("?x"), term("to"), term("?y")),)
    rules = [
        Rule("sg-base", (cond("edge", "?x", "to", "?p"),
                         cond("edge", "?y", "to", "?p",
                              tests=[("?x", "!=", "?y")])), head),
        Rule("sg-rec", (cond("edge", "?x", "to", "?a"),
                        cond("sg", "?a", "to", "?b"),
                        cond("edge", "?y", "to", "?b")), head)]
    engine = HiperfactEngine(dataclasses.replace(
        EngineConfig.infer1(backend), eval_mode="delta"))
    engine.add_rules(rules)
    engine.insert_facts(facts)
    engine.infer()
    want = plain_sg([(f.id, f.val) for f in facts], distinct_base=True)
    got = sg_pairs(engine)
    assert got == want
    assert got != plain_sg([(f.id, f.val) for f in facts],
                           distinct_base=False)
