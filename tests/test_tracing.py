"""Spans of the fact engine (``repro.tracing``) in a profiler trace.

A small RDFS-Plus materialization runs under ``jax.profiler``: every
span the closure path reaches shows up with its arguments, the transfer
spans account for every host<->device byte the backend counts, and
tracing leaves the fact set unchanged.  Small tc and sg closures check
the ``rows_out`` of the join spans.
"""

import dataclasses
import glob
import json
import os

import pytest

from bench import harness
from repro.core import EngineConfig, Fact, HiperfactEngine
from repro.core.rulesets import rdfs_plus_rules
from repro.tracing import SPANS

TRANSFER_SPANS = {"hf.d2h", "hf.h2d"}


def kg_facts():
    facts = [
        Fact("Schema", "A", "subClassOf", "B"),
        Fact("Schema", "B", "subClassOf", "C"),
        Fact("Schema", "C", "subClassOf", "D"),
        Fact("Schema", "knows", "characteristic", "symmetric"),
        Fact("Schema", "partOf", "characteristic", "transitive"),
        Fact("Schema", "worksFor", "subPropertyOf", "memberOf"),
        Fact("Data", "y", "type", "B"),
        Fact("Data", "x", "knows", "y"),
    ]
    facts += [Fact("Data", f"p{i}", "partOf", f"p{i + 1}") for i in range(6)]
    facts += [Fact("Data", f"s{i}", "worksFor", f"d{i % 3}")
              for i in range(12)]
    facts += [Fact("Data", f"s{i}", "type", "A") for i in range(12)]
    return facts


def engine(backend):
    e = HiperfactEngine(dataclasses.replace(EngineConfig.infer1(backend),
                                            eval_mode="delta"))
    e.add_rules(rdfs_plus_rules())
    return e


def fact_set(e):
    s = e.store.strings
    return {(ftype, s.lookup_id(int(t.ids[i])), s.lookup_id(int(t.attrs[i])),
             int(t.vals[i]))
            for ftype, t in e.store.tables.items()
            for i in range(t.n) if t.alive[i]}


def hf_events(trace_dir):
    """``(name, stats)`` of every ``hf.*`` host event in the trace."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    return [(e.name, dict(e.stats)) for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("hf.")]


@pytest.mark.parametrize("backend", ["numpy", "jax-interpret"])
def test_materialization_spans(backend, tmp_path):
    import jax

    e0 = engine(backend)
    e0.insert_facts(kg_facts())
    e0.infer()
    untraced = fact_set(e0)

    e = engine(backend)
    counter = getattr(e.ops, "transfers", None)
    before = counter.snapshot() if counter is not None else None
    jax.profiler.start_trace(str(tmp_path))
    try:
        e.insert_facts(kg_facts())
        st = e.infer()
    finally:
        jax.profiler.stop_trace()
    events = hf_events(str(tmp_path))

    assert fact_set(e) == untraced
    names = {n for n, _ in events}
    reachable = set(SPANS) - (TRANSFER_SPANS if counter is None else set())
    assert names == reachable, sorted(reachable ^ names)

    # one plan span per round checks the death frontiers; the others
    # plan one rule each
    per_rule = [(n, a) for n, a in events if n == "hf.rule"
                or (n == "hf.plan" and a["plan"] != "deaths")]
    assert per_rule
    for name, args in per_rule:
        assert {"round", "rule", "infer"} <= set(args), (name, args)
        assert 1 <= args["round"] <= st.iterations
    rounds = sorted(a["round"] for n, a in events if n == "hf.round")
    assert rounds == list(range(1, st.iterations + 1))
    plans = {a["plan"] for n, a in events if n == "hf.plan"}
    assert {"init", "delta"} <= plans, plans
    writes = [a for n, a in events if n == "hf.write"]
    assert writes and all("rows_fresh" in a for a in writes)

    d2h = [a["bytes"] for n, a in events if n == "hf.d2h"]
    if counter is not None:
        delta = counter.delta(before)
        assert len(d2h) == delta.d2h_calls > 0
        assert sum(d2h) == delta.d2h_bytes
        h2d = [a["bytes"] for n, a in events if n == "hf.h2d"]
        assert len(h2d) == delta.h2d_calls
        assert sum(h2d) == delta.h2d_bytes
    else:
        assert d2h == []


@pytest.mark.parametrize("backend", ["numpy", "jax-interpret"])
def test_counting_write_spans_count_lookups(backend, tmp_path, monkeypatch):
    """The counting path's ``hf.write`` spans carry ``match_rows`` (rows
    looked up on the device) and ``match_host`` (on the host); together
    they are the rows ``_apply_counts`` looked up in a table."""
    import jax

    looked_up = []
    apply_counts = HiperfactEngine._apply_counts

    def spy(self, ftype, ids, *rest):
        if self.store.table(ftype).n:
            looked_up.append(len(ids))
        return apply_counts(self, ftype, ids, *rest)

    monkeypatch.setattr(HiperfactEngine, "_apply_counts", spy)
    e = engine(backend)
    jax.profiler.start_trace(str(tmp_path))
    try:
        e.insert_facts(kg_facts())
        e.infer()
    finally:
        jax.profiler.stop_trace()
    counted = [a for n, a in hf_events(str(tmp_path))
               if n == "hf.write" and "match_rows" in a]
    assert counted and all("match_host" in a for a in counted)
    device = sum(a["match_rows"] for a in counted)
    host = sum(a["match_host"] for a in counted)
    assert device + host == sum(looked_up) > 0
    assert (host if backend == "numpy" else device) == sum(looked_up)


def test_span_costs_little_without_a_profiler():
    """With no profiler running a span is a cheap no-op context."""
    import time

    from repro.tracing import span
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        with span("hf.join", rows=i) as sp:
            sp.set_metadata(rows=i)
    assert (time.perf_counter() - t0) / n < 50e-6


def graph_engine(name, backend, nodes):
    """A fresh engine of the benchmark configuration ``name`` (``orb-tc``
    or ``orb-sg``) on ``backend``, and the base facts of its
    generator's ``nodes``-node graph of out-degree 5."""
    with open(os.path.join(harness.ROOT, "bench", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    cfg["scale"] = {"nodes": nodes, "edges": 5 * nodes}
    cfg["engine"]["backend"] = backend
    ds = harness.generator(cfg).generate(cfg, 3)
    return harness.make_engine(cfg), ds.fact_objects()


@pytest.mark.parametrize("backend", ["numpy", "jax-interpret"])
@pytest.mark.parametrize("name", ["orb-tc", "orb-sg"])
def test_join_spans_carry_rows_out(name, backend, tmp_path, monkeypatch):
    """Each ``hf.join`` span's ``rows_out`` is the row count of the
    binding table its step returned, read from a count the host already
    holds: the transfers of a closure are the same traced and not."""
    import jax

    from repro.core import islands

    returned = []
    join_step = islands._join_step

    def spy(*args, **kw):
        acc, still = join_step(*args, **kw)
        returned.append(acc.n)
        return acc, still

    def closure(trace_dir=None):
        e, facts = graph_engine(name, backend, 30)
        counter = getattr(e.ops, "transfers", None)
        if counter is not None:
            e.ops.cache.clear()
        before = counter.snapshot() if counter is not None else None
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        try:
            e.insert_facts(facts)
            e.infer()
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        moved = counter.delta(before) if counter is not None else None
        return e, moved

    _, untraced = closure()
    monkeypatch.setattr(islands, "_join_step", spy)
    e, traced = closure(str(tmp_path))
    assert traced == untraced
    rows_out = [a["rows_out"] for n, a in hf_events(str(tmp_path))
                if n == "hf.join"]
    assert sorted(rows_out) == sorted(returned) and sum(rows_out) > 0

    # one evaluation of the rule with most conditions: its first step
    # looks a condition up, each later step joins one in
    rule = max(e.rules, key=lambda r: len(r.conditions))
    returned.clear()
    trace_dir = str(tmp_path / "rule")
    jax.profiler.start_trace(trace_dir)
    try:
        out = islands.evaluate_rule(e.store, rule, ops=e.ops)
    finally:
        jax.profiler.stop_trace()
    rows_out = [a["rows_out"] for n, a in hf_events(trace_dir)
                if n == "hf.join"]
    assert len(rows_out) == len(rule.conditions) == (
        3 if name == "orb-sg" else 2)
    assert rows_out == returned and rows_out[-1] == out.n > 0
