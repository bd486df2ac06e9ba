"""The split of device idle time by the program's spans
(``bench/spans.py``), on made-up events."""

import pytest

from bench import spans

LAYERS = {"hf.load": "loader", "hf.infer": "fixpoint driver",
          "hf.round": "fixpoint driver", "hf.plan": "fixpoint driver",
          "hf.rule": "island joins", "hf.join": "island joins",
          "hf.write": "write side", "hf.index": "index and residency",
          "hf.d2h": "device backend"}
SHARES = ("fixpoint driver", "island joins", "write side",
          "index and residency", "device backend")


def events(span_list, ops=((10, 20), (50, 60))):
    return {"ops": {"/device:TPU:0": list(ops)},
            "spans": [(0, 100, "bench.window", {})] + [
                (s, e, name, args) for s, e, name, *rest in span_list
                for args in [rest[0] if rest else {}]]}


def test_idle_split_at_span_boundaries():
    # device busy [10, 20) and [50, 60): idle [0, 10), [20, 50), [60, 100)
    ev = events([(0, 100, "bench.infer"), (3, 95, "hf.infer"),
                 (4, 45, "hf.round"), (26, 35, "hf.plan"),
                 (40, 55, "hf.d2h", {"bytes": 64})])
    r = spans.reduce_events(ev, LAYERS)
    ns = {k: pytest.approx(v * 1e-9) for k, v in {
        "bench.infer": 3 + 5, "hf.infer": 1 + 35, "hf.round": 6 + 6 + 5,
        "hf.plan": 9, "hf.d2h": 10}.items()}
    assert r["by_span"] == ns
    assert r["idle_share"] == pytest.approx(80.0)
    assert r["layer_share"]["fixpoint driver"] == pytest.approx(62.0)
    assert r["layer_share"]["device backend"] == pytest.approx(10.0)
    assert r["layer_share"][spans.UNATTRIBUTED] == pytest.approx(8.0)
    assert r["d2h_calls"] == 1 and r["d2h_bytes"] == 64
    assert r["infers"] == 1
    # the gaps, longest first, each named by the span holding most of it
    assert [g[0] for g in r["top_gaps"]] == ["hf.infer", "hf.round",
                                             "hf.round"]


def test_innermost_span_across_threads():
    # a rule on one pool thread, a join and a write on two others that
    # overlap without nesting: each piece goes to the shortest open span
    ev = events([(0, 100, "hf.infer"), (20, 80, "hf.rule"),
                 (30, 40, "hf.join"), (35, 70, "hf.write")],
                ops=[(0, 30), (75, 100)])
    r = spans.reduce_events(ev, LAYERS)
    assert r["by_span"] == {"hf.join": pytest.approx(10e-9),
                            "hf.write": pytest.approx(30e-9),
                            "hf.rule": pytest.approx(5e-9)}
    assert r["idle_share"] == pytest.approx(45.0)


def test_shares_add_up_to_the_idle_share():
    from bench import trace
    ev = events([(0, 30, "bench.load"), (2, 28, "hf.load"),
                 (3, 9, "hf.write"), (4, 8, "hf.index"),
                 (30, 100, "bench.infer"), (31, 99, "hf.infer"),
                 (32, 60, "hf.round"), (33, 50, "hf.rule"),
                 (34, 45, "hf.join"), (46, 49, "hf.d2h"),
                 (62, 98, "hf.round"), (63, 70, "hf.plan")],
                ops=[(5, 6), (12, 14), (40, 41), (52, 55), (80, 85)])
    r = spans.reduce_events(ev, LAYERS)
    parts = sum(r["layer_share"].get(k, 0.0) for k in SHARES)
    parts += r["layer_share"]["loader"]
    parts += r["layer_share"][spans.UNATTRIBUTED]
    assert parts == pytest.approx(r["idle_share"])
    ops = {p: [(s, e, "op") for s, e in iv] for p, iv in ev["ops"].items()}
    whole = trace.reduce_events({"ops": ops, "programs": {},
                                 "spans": [sp[:3] for sp in ev["spans"]]})
    assert r["idle_share"] == pytest.approx(100.0 * whole["idle_share"])


def test_no_program_spans_reads_nothing():
    assert spans.reduce_events(events([(0, 100, "bench.infer")]),
                               LAYERS) is None


@pytest.mark.parametrize("metric", [
    "fixpoint.idle_share.closure", "joins.idle_share.closure",
    "write.idle_share.closure", "index.idle_share.closure",
    "backend.transfer_idle_share.closure", "backend.host_syncs.closure"])
def test_reader_without_a_trace_reads_nothing(metric):
    from bench import harness
    read = harness.load_metric(metric)
    assert read({"trace": None, "units": 3}) is None
    assert read({}) is None
