"""Semi-naive delta fixpoint (ISSUE 4): delta ≡ full, O(Δ) rounds.

The delta evaluator must be a pure performance axis: for every join /
unique-filter / backend combination, streaming appends through
``eval_mode="delta"`` must converge to the same fact set and the same
query results as ``eval_mode="full"`` — including the fallback cases
(deletes/tombstones, external actions) where delta silently reverts to
full evaluation.  On the device backend, an empty-delta round must cost
zero host<->device transfers, and delta-window state must never pollute
the uid memo (transient handles).
"""

import dataclasses

import numpy as np
import pytest

from repro.core import EngineConfig, Fact, HiperfactEngine, Rule
from repro.core.conditions import AddAction, DeleteAction, cond, term
from repro.core.facts import ValueType
from repro.core.rulesets import rdfs_plus_rules


def kg_facts():
    return [
        Fact("Schema", "A", "subClassOf", "B"),
        Fact("Schema", "B", "subClassOf", "C"),
        Fact("Schema", "C", "subClassOf", "D"),
        Fact("Schema", "knows", "characteristic", "symmetric"),
        Fact("Schema", "partOf", "characteristic", "transitive"),
        Fact("Data", "x", "type", "A"),
        Fact("Data", "y", "type", "B"),
        Fact("Data", "x", "knows", "y"),
        Fact("Data", "p1", "partOf", "p2"),
        Fact("Data", "p2", "partOf", "p3"),
    ]


def stream_batches():
    return [
        [Fact("Data", "p3", "partOf", "p4"),
         Fact("Data", "z", "type", "A")],
        [Fact("Data", "y", "knows", "z"),
         Fact("Schema", "D", "subClassOf", "E")],
        [Fact("Data", "p4", "partOf", "p5")],
    ]


def fact_set(engine):
    out = set()
    for ftype, t in engine.store.tables.items():
        alive = t.alive
        for i in range(t.n):
            if alive[i]:
                out.add((ftype, int(t.ids[i]), int(t.attrs[i]),
                         int(t.vals[i])))
    return out


def decoded_fact_set(engine):
    """Backend-independent form (string ids resolved)."""
    s = engine.store.strings
    out = set()
    for ftype, t in engine.store.tables.items():
        alive = t.alive
        for i in range(t.n):
            if alive[i]:
                out.add((ftype, s.lookup_id(int(t.ids[i])),
                         s.lookup_id(int(t.attrs[i])), int(t.vals[i])))
    return out


def run_streaming(cfg):
    e = HiperfactEngine(cfg)
    e.add_rules(rdfs_plus_rules())
    e.insert_facts(kg_facts())
    e.infer()
    for batch in stream_batches():
        e.insert_facts(batch)
        e.infer()
    return e


GRID = [(j, u) for j in ("MJ", "HJ") for u in ("SU", "HU")]


@pytest.mark.parametrize("join,unique", GRID, ids=lambda v: v)
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_delta_full_parity_streaming(join, unique, backend):
    """Identical inferred facts across eval modes for MJ/HJ × SU/HU on
    both backends, under streaming appends."""
    base = EngineConfig(index_backend="AI", join=join, unique=unique,
                        backend=backend)
    e_full = run_streaming(dataclasses.replace(base, eval_mode="full"))
    e_delta = run_streaming(dataclasses.replace(base, eval_mode="delta"))
    assert fact_set(e_full) == fact_set(e_delta)
    q = [cond("Data", "?x", "type", "?t")]
    got_f = {tuple(sorted(r.items())) for r in e_full.query(q)}
    got_d = {tuple(sorted(r.items())) for r in e_delta.query(q)}
    assert got_f == got_d


def test_delta_cross_backend_parity():
    """numpy/delta ≡ jax/delta on the decoded fact set."""
    base = EngineConfig(index_backend="AI", join="MJ", unique="SU",
                        eval_mode="delta")
    e_np = run_streaming(dataclasses.replace(base, backend="numpy"))
    e_jx = run_streaming(dataclasses.replace(base, backend="jax"))
    assert decoded_fact_set(e_np) == decoded_fact_set(e_jx)


def test_empty_delta_round_no_evaluations():
    """A round with no appends evaluates nothing: every rule is skipped
    as unchanged and no rows are considered."""
    cfg = EngineConfig(eval_mode="delta")
    e = HiperfactEngine(cfg)
    e.add_rules(rdfs_plus_rules())
    e.insert_facts(kg_facts())
    e.infer()
    s = e.infer()
    assert s.facts_inferred == 0
    assert s.rules_evaluated == 0
    assert s.rows_considered == 0


def test_empty_delta_round_zero_transfers():
    """Acceptance: an empty-delta round on the device backend performs
    zero h2d/d2h transfers."""
    cfg = EngineConfig(index_backend="AI", join="MJ", unique="SU",
                       backend="jax-interpret", eval_mode="delta")
    e = HiperfactEngine(cfg)
    e.add_rules(rdfs_plus_rules())
    e.insert_facts(kg_facts())
    e.infer()
    snap = e.ops.transfers.snapshot()
    s = e.infer()  # nothing appended since the last round
    d = e.ops.transfers.delta(snap)
    assert s.facts_inferred == 0
    assert d.h2d_calls == 0 and d.d2h_calls == 0, d


def test_delta_rounds_skip_unrelated_appends():
    """Appending facts that match no condition's constants runs no
    delta passes (the O(Δ) frontier scan filters them out)."""
    cfg = EngineConfig(eval_mode="delta")
    e = HiperfactEngine(cfg)
    rule = Rule("r", (cond("T", "?x", "likes", "?y"),),
                (AddAction("T", term("?y"), "likedBy", term("?x")),))
    e.add_rule(rule)
    e.insert_facts([Fact("T", "a", "likes", "b")])
    e.infer()
    e.insert_facts([Fact("T", "c", "other", "d")])
    s = e.infer()
    assert s.facts_inferred == 0
    assert s.delta_passes == 0  # frontier scan found nothing for 'likes'


def test_delta_uses_deltas_not_full(monkeypatch):
    """After the first fixpoint, re-infer on a small append considers
    far fewer rows than a full evaluation."""
    cfg = EngineConfig(eval_mode="delta")
    e = HiperfactEngine(cfg)
    e.add_rules(rdfs_plus_rules())
    e.insert_facts(kg_facts() * 1)
    s_initial = e.infer()
    e.insert_facts([Fact("Data", "q", "type", "A")])
    s = e.infer()
    assert s.full_evals == 0  # every evaluation ran as delta passes
    assert s.delta_passes > 0
    assert 0 < s.rows_considered < s_initial.rows_considered


def test_delete_propagates_as_signed_frontier():
    """Tombstones no longer void the frontier: a deleted base fact rides
    the −frontier, the derived fact's support collapses, and the result
    matches full mode with zero full re-evaluations."""
    def build(mode):
        e = HiperfactEngine(EngineConfig(eval_mode=mode))
        e.insert_facts([Fact("T", f"n{i}", "flag", "on")
                        for i in range(6)] +
                       [Fact("T", "kill", "flag", "off")])
        e.add_rule(Rule("fan", (cond("T", "?x", "flag", "on"),),
                        (AddAction("T", term("?x"), "seen", "yes"),)))
        e.infer()
        # delete a base fact, then append more: the delete log slice is
        # the −frontier of the next evaluation
        e.delete_facts([Fact("T", "n0", "flag", "on")])
        e.insert_facts([Fact("T", "n9", "flag", "on")])
        s = e.infer()
        return e, s
    (e_full, _), (e_delta, s_delta) = build("full"), build("delta")
    assert fact_set(e_full) == fact_set(e_delta)
    assert s_delta.full_evals == 0       # steady state stays delta
    assert s_delta.neg_passes > 0        # the retraction ran as a pass
    assert s_delta.facts_retracted == 1  # n0's "seen" fact died
    assert s_delta.dred_scrubs == 0      # counting, not over-deletion
    assert e_delta.query([cond("T", "?x", "seen", "yes")]) == e_full.query(
        [cond("T", "?x", "seen", "yes")])


def test_delete_action_rules_run_as_delta():
    """Delete-action rules are idempotent: +frontier passes are sound,
    so steady-state rounds keep ``full_evals == 0`` (and still converge
    identically to full mode)."""
    def build(mode):
        e = HiperfactEngine(EngineConfig(eval_mode=mode))
        e.insert_facts([Fact("T", "a", "flag", "off"),
                        Fact("T", "b", "flag", "on")])
        e.add_rule(Rule("del-off", (cond("T", "?x", "flag", "off"),),
                        (DeleteAction("T", term("?x"), "flag", "off"),)))
        e.infer()
        e.insert_facts([Fact("T", "c", "flag", "off")])
        s = e.infer()
        return e, s
    (e_full, _), (e_delta, s_delta) = build("full"), build("delta")
    assert fact_set(e_full) == fact_set(e_delta)
    assert s_delta.full_evals == 0   # delete rules ride +frontier passes
    assert s_delta.delta_passes > 0
    q = [cond("T", "?x", "flag", "off")]
    assert e_delta.query(q) == []


def test_eval_mode_validation():
    with pytest.raises(ValueError):
        HiperfactEngine(EngineConfig(eval_mode="bogus"))


def test_infer_stats_rounds():
    e = HiperfactEngine(EngineConfig(eval_mode="delta"))
    e.add_rules(rdfs_plus_rules())
    e.insert_facts(kg_facts())
    s = e.infer()
    assert len(s.rounds) == s.iterations
    assert sum(r["rows_emitted"] for r in s.rounds) == s.facts_inferred
    assert sum(r["rows_considered"] for r in s.rounds) == s.rows_considered


# ---------------------------------------------------------------------------
# Device-side join tests (ISSUE 4 satellite): var⊕var and var⊕const stay
# resident on the pipeline


def age_facts():
    return [Fact("AgeClass", "kid", "minAge", 0, ValueType.UINT32),
            Fact("AgeClass", "adult", "minAge", 18, ValueType.UINT32),
            Fact("Person", "p1", "age", 7, ValueType.UINT32),
            Fact("Person", "p2", "age", 30, ValueType.UINT32),
            Fact("Person", "p3", "age", 18, ValueType.UINT32)]


@pytest.mark.parametrize("backend", ["numpy", "jax-interpret"])
def test_join_test_var_const(backend):
    e = HiperfactEngine(EngineConfig(index_backend="AI", join="MJ",
                                     unique="SU", backend=backend))
    e.insert_facts(age_facts())
    rows = e.query([cond("Person", "?p", "age", "?a", ValueType.UINT32,
                         tests=[("?a", ">=", 18)])])
    assert {(r["p"], r["a"]) for r in rows} == {("p2", 30), ("p3", 18)}


@pytest.mark.parametrize("backend", ["numpy", "jax-interpret"])
def test_join_test_double_decode(backend):
    """Ordered compare on DOUBLE lanes decodes the bit-pun (negative
    floats order wrong as raw int64)."""
    e = HiperfactEngine(EngineConfig(index_backend="AI", join="MJ",
                                     unique="SU", backend=backend))
    e.insert_facts([Fact("M", "a", "w", 1.5, ValueType.DOUBLE),
                    Fact("M", "b", "w", -2.5, ValueType.DOUBLE),
                    Fact("M", "c", "w", 0.25, ValueType.DOUBLE)])
    rows = e.query([cond("M", "?x", "w", "?w", ValueType.DOUBLE,
                         tests=[("?w", "<", 1.0)])])
    assert {r["x"] for r in rows} == {"b", "c"}


def test_join_test_repeat_zero_transfers():
    """A repeated test-bearing query at a fixed version is a pure memo
    walk — the device compare + compaction never leave the device."""
    e = HiperfactEngine(EngineConfig(index_backend="AI", join="MJ",
                                     unique="SU", backend="jax-interpret"))
    e.insert_facts(age_facts())
    q = [cond("AgeClass", "?ac", "minAge", "?m", ValueType.UINT32),
         cond("Person", "?p", "age", "?a", ValueType.UINT32,
              tests=[("?a", ">=", "?m")])]
    e.query(q, decode=False)
    snap = e.ops.transfers.snapshot()
    b = e.query(q, decode=False)
    d = e.ops.transfers.delta(snap)
    assert b.n == 5  # kid x (p1,p2,p3) + adult x (p2,p3)
    assert d.h2d_calls == 0 and d.d2h_calls == 0, d


def test_rete_oracle_const_test():
    """The Rete baseline understands var⊕const tests identically."""
    from repro.core.rete_baseline import ReteEngine

    r = ReteEngine()
    r.add_rule(Rule("q", (cond("Person", "?p", "age", "?a",
                               ValueType.UINT32, tests=[("?a", "<", 18)]),)))
    r.insert(age_facts())
    r.infer()
    got = {m["p"] for m in r.query([
        cond("Person", "?p", "age", "?a", ValueType.UINT32,
             tests=[("?a", "<", 18)])])}
    assert got == {"p1"}


# ---------------------------------------------------------------------------
# Delta-only uploads + transient handles on the device backend


def fresh_jax_ops():
    from repro.backend.jax_ops import JaxOps
    return JaxOps(mode="interpret", block=256)


def test_upload_resident_extends_with_delta_only():
    ops = fresh_jax_ops()
    rng = np.random.RandomState(7)
    col = rng.randint(0, 1000, 4000).astype(np.int64)
    h1 = ops.upload_resident(("t", 1), 1, col)
    ext = np.concatenate([col, rng.randint(0, 1000, 50).astype(np.int64)])
    snap = ops.transfers.snapshot()
    h2 = ops.upload_resident(("t", 1), 2, ext)
    d = ops.transfers.delta(snap)
    assert 0 < d.h2d_bytes < col.nbytes // 4, d  # tail only
    np.testing.assert_array_equal(h2.host(), ext)
    assert ops.cache.stats()["extended"] >= 1
    # same version again: the exact cached handle, zero transfers
    snap = ops.transfers.snapshot()
    h3 = ops.upload_resident(("t", 1), 2, ext)
    assert h3 is h2
    d = ops.transfers.delta(snap)
    assert d.h2d_calls == 0 and d.d2h_calls == 0


def test_upload_resident_rewrite_detected():
    """A column whose prefix changed (not append-only) re-uploads in
    full — the memcmp guard rejects the extension."""
    ops = fresh_jax_ops()
    col = np.arange(2000, dtype=np.int64)
    ops.upload_resident(("t", 2), 1, col)
    mutated = col.copy()
    mutated[0] = -99
    mutated = np.concatenate([mutated, np.asarray([1, 2], np.int64)])
    h = ops.upload_resident(("t", 2), 2, mutated)
    np.testing.assert_array_equal(h.host(), mutated)


def test_transient_handles_skip_memo():
    """Ops over transient (delta-window) handles do not populate the
    uid memo; ops over stable handles still do."""
    ops = fresh_jax_ops()
    a = np.arange(100, dtype=np.int64)
    stable = ops.upload(a)
    transient = ops.upload_resident(("w", 1), 1, a, transient=True)
    assert stable.stable and not transient.stable
    idx = ops.iota_h(10)  # memoized on creation, before the snapshot
    before = ops.cache.stats()["entries"]
    out = ops.gather_h(transient, idx, 10)
    assert not out.stable  # transience propagates
    ops.semi_join_h(transient, stable)
    assert ops.cache.stats()["entries"] == before
    # stable chain: memoized, repeat returns the same handle
    g1 = ops.gather_h(stable, idx, 10)
    g2 = ops.gather_h(stable, idx, 10)
    assert g1 is g2


# ---------------------------------------------------------------------------
# The counting write side looks facts up by their whole (id, attr, val)


TC_RULES = [
    Rule("tc-base", (cond("edge", "?x", "to", "?y"),),
         (AddAction("path", term("?x"), "to", term("?y")),)),
    Rule("tc-rec", (cond("edge", "?x", "to", "?y"),
                    cond("path", "?y", "to", "?z")),
         (AddAction("path", term("?x"), "to", term("?z")),)),
]
TWO_HOP_RULES = [
    Rule("hop2", (cond("edge", "?x", "to", "?y"),
                  cond("edge", "?y", "to", "?z")),
         (AddAction("hop2", term("?x"), "to", term("?z")),)),
]
CHAIN = [Fact("edge", f"c{i}", "to", f"c{i + 1}") for i in range(5)]


def dag_edges(nodes=24, edges=60, seed=3):
    """A random DAG: distinct pairs oriented low to high."""
    rng = np.random.RandomState(seed)
    pairs = set()
    while len(pairs) < edges:
        a, b = sorted(rng.choice(nodes, 2, replace=False).tolist())
        pairs.add((a, b))
    return [Fact("edge", f"n{a}", "to", f"n{b}") for a, b in sorted(pairs)]


def counting_engine(backend, rules):
    e = HiperfactEngine(dataclasses.replace(EngineConfig.infer1(backend),
                                            eval_mode="delta"))
    e.add_rules(rules)
    return e


@pytest.mark.parametrize("ruleset", ["tc", "rdfs-plus"])
def test_counting_write_side_looks_up_rows_on_device(ruleset, monkeypatch):
    """On the jax backend the counting path's membership test is
    ``JaxOps.match_rows``: ``_apply_counts`` never reaches
    ``join_pairs``, and the fact set is the numpy engine's."""
    from repro.backend.jax_ops import JaxOps
    if ruleset == "tc":
        rules, facts = TC_RULES, dag_edges()
    else:
        rules = rdfs_plus_rules()
        facts = kg_facts() + [f for b in stream_batches() for f in b]
    want = counting_engine("numpy", rules)
    want.insert_facts(facts)
    want.infer()

    inside = []
    calls = {"join_pairs": 0, "match_rows": 0}

    def spy(cls, name):
        orig = getattr(cls, name)

        def wrapped(self, *args, **kwargs):
            if name in calls and inside:
                calls[name] += 1
            if name != "_apply_counts":
                return orig(self, *args, **kwargs)
            inside.append(True)
            try:
                return orig(self, *args, **kwargs)
            finally:
                inside.pop()
        monkeypatch.setattr(cls, name, wrapped)

    spy(HiperfactEngine, "_apply_counts")
    spy(JaxOps, "join_pairs")
    spy(JaxOps, "match_rows")
    got = counting_engine("jax-interpret", rules)
    got.insert_facts(facts)
    got.infer()
    assert decoded_fact_set(got) == decoded_fact_set(want)
    assert calls["join_pairs"] == 0 and calls["match_rows"] > 0, calls


def alive_support(engine):
    """Support and assertion of every alive fact, by decoded triple."""
    s = engine.store.strings
    return {(ftype, s.lookup_id(int(t.ids[r])), s.lookup_id(int(t.attrs[r])),
             int(t.vals[r])): (int(t.support[r]), bool(t.asserted[r]))
            for ftype, t in engine.store.tables.items()
            for r in np.flatnonzero(t.alive)}


@pytest.mark.parametrize("ruleset", ["tc", "two-hop"])
def test_reasserted_facts_match_their_newest_row(ruleset):
    """Retracting a base edge kills derived rows; re-asserting it brings
    the same triples back as new rows.  At most one copy of a triple is
    alive and it is the newest, so ``_match_rows`` (the last equal row,
    kept if alive) finds exactly the alive copy; support counts equal
    the numpy engine's."""
    from repro.core.engine import _match_rows
    rules, out = ((TC_RULES, "path") if ruleset == "tc"
                  else (TWO_HOP_RULES, "hop2"))
    gone = CHAIN[2]
    engines = {}
    for backend in ("numpy", "jax-interpret"):
        e = counting_engine(backend, rules)
        e.insert_facts(dag_edges() + CHAIN)
        e.infer()
        n_before = e.store.tables[out].n
        e.delete_facts([gone])
        e.infer()
        assert e.store.tables[out].n_dead > 0
        e.insert_facts([gone])
        e.infer()
        assert e.store.tables[out].n > n_before
        engines[backend] = e
    assert alive_support(engines["jax-interpret"]) == alive_support(
        engines["numpy"])

    for e in engines.values():
        copies = 0
        for t in e.store.tables.values():
            newest, alive_row = {}, {}
            triples = list(zip(t.ids.tolist(), t.attrs.tolist(),
                               t.vals.tolist()))
            for r, k in enumerate(triples):
                newest[k] = r
                if t.alive[r]:
                    assert k not in alive_row, "two alive copies"
                    alive_row[k] = r
            assert all(newest[k] == r for k, r in alive_row.items())
            copies += len(triples) - len(newest)
            rowof = _match_rows(t, t.ids, t.attrs, t.vals, e.ops,
                                e._pk_memo)
            np.testing.assert_array_equal(
                rowof, [alive_row.get(k, -1) for k in triples])
        assert copies > 0  # some triple has a dead older copy
