"""Demand-driven evaluation (ISSUE 9): query-time magic-set cone ≡ full.

``EngineConfig(eval_mode="demand")`` routes ``query()`` through a
demand transformation — the query constants seed per-type demand
frontiers, restriction propagates backward through the producing rules,
and only the demanded cone is materialized.  The contract: decoded
query results identical to ``eval_mode="full"`` / ``"delta"`` across
shard counts and backends, with the *rest of the store untouched*; the
fallback ladder (existence gates, external actions, unknown constants,
delete rules) silently reverts to full evaluation, never to a wrong
answer.
"""

import dataclasses

import pytest

from repro.core import EngineConfig, Fact, HiperfactEngine, Rule
from repro.core.conditions import (AddAction, DeleteAction, ExternalAction,
                                   cond, term)
from repro.core.demand import DemandEvaluator

K_CHAINS, CHAIN_LEN = 3, 5


def chain_facts(k=K_CHAINS, length=CHAIN_LEN):
    return [Fact("edge", f"c{j}_n{i}", "to", f"c{j}_n{i + 1}")
            for j in range(k) for i in range(length)]


def closure_rules():
    return [
        Rule("base", (cond("edge", "?x", "to", "?y"),),
             (AddAction("path", term("?x"), "to", term("?y")),)),
        Rule("rec", (cond("edge", "?x", "to", "?y"),
                     cond("path", "?y", "to", "?z")),
             (AddAction("path", term("?x"), "to", term("?z")),)),
    ]


POINT_Q = [cond("path", "c0_n0", "to", "?z")]


def q_rows(engine, conditions=POINT_Q):
    return sorted(tuple(sorted(r.items()))
                  for r in engine.query(conditions))


def _cfg(backend="numpy", **kw):
    return dataclasses.replace(EngineConfig.infer1(backend), **kw)


def _build(cfg, facts=None, rules=None):
    e = HiperfactEngine(cfg)
    e.add_rules(rules if rules is not None else closure_rules())
    e.insert_facts(facts if facts is not None else chain_facts())
    return e


def _reference_rows():
    e = _build(_cfg(eval_mode="full"))
    e.infer()
    return q_rows(e)


# ---------------------------------------------------------------------------
# Parity: demand ≡ delta ≡ full across shards and backends


@pytest.mark.parametrize("backend", ["numpy", "jax-interpret"])
@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("mode", ["full", "delta", "demand"])
def test_point_query_parity(mode, shards, backend, on_devices):
    if backend != "numpy" and shards > 1:
        on_devices(check_point_query_parity, mode, shards, backend,
                   n_devices=shards)
    else:
        check_point_query_parity(mode, shards, backend)


def check_point_query_parity(mode, shards, backend):
    ref = _reference_rows()
    e = _build(_cfg(backend, eval_mode=mode, shards=shards))
    if mode != "demand":
        e.infer()                      # demand engines stay cold
    assert q_rows(e) == ref
    # streaming append into the queried chain invalidates the demand
    # memo / delta watermark alike; results must track
    e.insert_facts([Fact("edge", f"c0_n{CHAIN_LEN}", "to",
                         f"c0_n{CHAIN_LEN + 1}")])
    if mode != "demand":
        e.infer()
    rows2 = q_rows(e)
    assert len(rows2) == len(ref) + 1
    e2 = _build(_cfg(eval_mode="full"),
                facts=chain_facts() + [Fact("edge", f"c0_n{CHAIN_LEN}",
                                            "to", f"c0_n{CHAIN_LEN + 1}")])
    e2.infer()
    assert rows2 == q_rows(e2)


def test_demand_touches_only_the_cone():
    e = _build(_cfg(eval_mode="demand"))
    assert q_rows(e) == _reference_rows()
    st = e.last_infer
    assert st.demand_fallbacks == 0
    assert st.demand_cone_rows > 0
    # the untouched chains were never materialized: no path fact may
    # mention a c1_/c2_ node
    s = e.store.strings
    t = e.store.tables.get("path")
    ids = {s.lookup_id(int(t.ids[i])) for i in range(t.n) if t.alive[i]}
    assert ids and all(i.startswith("c0_") for i in ids)


def test_demand_restriction_beats_full_rows_considered():
    e_full = _build(_cfg(eval_mode="full"))
    e_full.infer()
    q_rows(e_full)
    full_rows = e_full.last_infer.rows_considered
    e = _build(_cfg(eval_mode="demand"))
    q_rows(e)
    assert 0 < e.last_infer.rows_considered < full_rows


def test_demand_memo_and_query_cache():
    e = _build(_cfg(eval_mode="demand"))
    q_rows(e)
    rounds = e.last_infer.demand_rounds
    n_facts = e.store.num_facts()
    # re-query at fixed versions: query-cache hit, no new demand rounds,
    # no new facts
    rows = e.query(POINT_Q)
    assert e.last_infer.query_cache_hits >= 1
    assert e.last_infer.demand_rounds == rounds
    assert e.store.num_facts() == n_facts
    # mutating a returned row must not poison the cache (frozen entries)
    rows[0]["z"] = "mutant"
    assert sorted(tuple(sorted(r.items())) for r in e.query(POINT_Q)) \
        == _reference_rows()


def test_sketch_planner_parity_and_counters():
    base = _cfg(eval_mode="full")
    ref = _build(base)
    ref.infer()
    e = _build(dataclasses.replace(base, sort_mode="sketch"))
    st = e.infer()
    assert st.sketch_hits + st.sketch_misses > 0
    assert q_rows(e) == q_rows(ref)


# ---------------------------------------------------------------------------
# Fallback ladder: wrong-shaped cones revert to full evaluation


def test_fallback_unknown_constant():
    e = _build(_cfg(eval_mode="demand"))
    q = [cond("path", "never_interned", "to", "?z")]
    assert DemandEvaluator(e, q).fallback == "unknown-constant"
    assert e.query(q) == []
    assert e.last_infer.demand_fallbacks == 1


def test_fallback_no_constants():
    e = _build(_cfg(eval_mode="demand"))
    q = [cond("path", "?x", "?a", "?z")]  # every slot a variable
    assert DemandEvaluator(e, q).fallback == "no-constants"
    rows = e.query(q)
    assert e.last_infer.demand_fallbacks == 1
    full = _build(_cfg(eval_mode="full"))
    full.infer()
    assert sorted(map(repr, rows)) == sorted(map(repr, full.query(q)))


def test_fallback_existence_gate():
    rules = closure_rules() + [
        Rule("gated", (cond("Flag", "on", "enabled", "yes"),
                       cond("edge", "?x", "to", "?y"),),
             (AddAction("path", term("?y"), "to", term("?x")),))]
    facts = chain_facts() + [Fact("Flag", "on", "enabled", "yes")]
    e = _build(_cfg(eval_mode="demand"), facts=facts, rules=rules)
    assert DemandEvaluator(e, POINT_Q).fallback == "existence-gate"
    rows = q_rows(e)
    assert e.last_infer.demand_fallbacks == 1
    full = _build(_cfg(eval_mode="full"), facts=facts, rules=rules)
    full.infer()
    assert rows == q_rows(full)


def test_fallback_external_action():
    seen = []
    rules = [Rule("base", (cond("edge", "?x", "to", "?y"),),
                  (AddAction("path", term("?x"), "to", term("?y")),
                   ExternalAction(lambda b: seen.append(1))))]
    e = _build(_cfg(eval_mode="demand"), rules=rules)
    assert DemandEvaluator(e, POINT_Q).fallback == "external-action"
    rows = q_rows(e)
    assert e.last_infer.demand_fallbacks == 1
    assert seen  # the sink fired — full evaluation really ran
    assert len(rows) == 1  # base rule only: the single outgoing edge


def test_fallback_foreign_delete():
    rules = closure_rules() + [
        Rule("purge", (cond("Tomb", "?x", "dead", "yes"),),
             (DeleteAction("path", term("?x"), "to", "gone"),))]
    e = _build(_cfg(eval_mode="demand"), rules=rules)
    assert DemandEvaluator(e, POINT_Q).fallback == "foreign-delete"
    rows = q_rows(e)
    assert e.last_infer.demand_fallbacks == 1
    assert rows == _reference_rows()


# ---------------------------------------------------------------------------
# Served variants (ISSUE 10): every rung of the fallback ladder answered
# through a FactServer must stay checksum-identical to full evaluation


def _mixed_action_rules():
    # a cone rule whose actions are not all adds: the "delete-action"
    # rung (distinct from "foreign-delete": the deleter is *inside* the
    # producing cone here)
    return closure_rules() + [
        Rule("mix", (cond("edge", "?x", "to", "?y"),),
             (AddAction("path", term("?y"), "to", term("?x")),
              DeleteAction("Scratch", term("?x"), "dead", "yes")))]


_SERVED_FALLBACKS = {
    "unknown-constant": (
        closure_rules, chain_facts,
        [cond("path", "never_interned_served", "to", "?z")]),
    "no-constants": (
        closure_rules, chain_facts, [cond("path", "?x", "?a", "?z")]),
    "existence-gate": (
        lambda: closure_rules() + [
            Rule("gated", (cond("Flag", "on", "enabled", "yes"),
                           cond("edge", "?x", "to", "?y"),),
                 (AddAction("path", term("?y"), "to", term("?x")),))],
        lambda: chain_facts() + [Fact("Flag", "on", "enabled", "yes")],
        POINT_Q),
    "external-action": (
        lambda: [Rule("base", (cond("edge", "?x", "to", "?y"),),
                      (AddAction("path", term("?x"), "to", term("?y")),
                       ExternalAction(lambda b: None)))],
        chain_facts, POINT_Q),
    "delete-action": (_mixed_action_rules, chain_facts, POINT_Q),
    "foreign-delete": (
        lambda: closure_rules() + [
            Rule("purge", (cond("Tomb", "?x", "dead", "yes"),),
                 (DeleteAction("path", term("?x"), "to", "gone"),))],
        chain_facts, POINT_Q),
}


@pytest.mark.parametrize("reason", sorted(_SERVED_FALLBACKS))
def test_served_fallback_parity(reason):
    from repro.serve import FactServer

    rules_fn, facts_fn, q = _SERVED_FALLBACKS[reason]
    e = _build(_cfg(eval_mode="demand"), facts=facts_fn(),
               rules=rules_fn())
    assert DemandEvaluator(e, q).fallback == reason
    full = _build(_cfg(eval_mode="full"), facts=facts_fn(),
                  rules=rules_fn())
    full.infer()
    ref = sorted(tuple(sorted(r.items())) for r in full.query(q))
    with FactServer(e, batching=False) as srv:
        first = srv.serve(q)
        assert sorted(tuple(sorted(r.items())) for r in first.rows) == ref
        assert e.last_infer.demand_fallbacks >= 1
        again = srv.serve(q)  # repeat at unchanged frontier
        assert again.checksum() == first.checksum()


def test_served_probe_cap_escalation_under_concurrent_append(monkeypatch):
    """A served query whose demand sets outgrow PROBE_CAP mid-flight —
    while a writer streams cold appends — must escalate to unrestricted
    demand and stay checksum-identical to a full-evaluation replay of
    the exact write prefix behind each served token."""
    import threading

    import repro.core.demand as demand_mod
    from repro.serve import FactServer

    monkeypatch.setattr(demand_mod, "PROBE_CAP", 2)
    e = _build(_cfg(eval_mode="demand"))
    # sanity: with the tiny cap, this cone really escalates
    ev = DemandEvaluator(e, POINT_Q)
    assert ev.fallback is None
    while ev.round():
        pass
    assert any(d.all for d in ev.demand.values()), "no escalation hit"

    e2 = _build(_cfg(eval_mode="demand"))
    extra = [Fact("edge", f"c0_n{CHAIN_LEN + i}", "to",
                  f"c0_n{CHAIN_LEN + i + 1}") for i in range(6)]
    with FactServer(e2, batching=False, record_history=True) as srv:
        served = []

        def writer():
            for f in extra:
                srv.append([f])       # demand default: no infer (cold)

        def reader():
            for _ in range(8):
                served.append(srv.serve(POINT_Q))

        ts = [threading.Thread(target=writer),
              threading.Thread(target=reader)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        final = srv.serve(POINT_Q)
        history = srv.history

    # oracle: replay each history prefix on a full engine
    by_token = {}
    writes: list = []
    for kind, facts, tok in history:
        if facts:
            writes.append((kind, facts))
        o = _build(_cfg(eval_mode="full"))
        o.infer()
        for kind2, fs in writes:
            (o.insert_facts if kind2 == "append" else o.delete_facts)(fs)
            o.infer()
        by_token[tok] = sorted(tuple(sorted(r.items()))
                               for r in o.query(POINT_Q))
    for res in served + [final]:
        assert res.token in by_token, "torn read: token outside history"
        got = sorted(tuple(sorted(r.items())) for r in res.rows)
        assert got == by_token[res.token]
    assert len(final.rows) == CHAIN_LEN + len(extra)
