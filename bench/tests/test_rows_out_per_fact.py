"""``joins.rows_out_per_fact.closure``: binding rows the island joins
built per fact derived, from the ``hf.join`` and ``hf.write`` spans'
arguments, on made-up events."""

import pytest

from bench import harness, spans

METRIC = "joins.rows_out_per_fact.closure"


def events(spans_, window=(0, 1000)):
    """``spans.load``-shaped events: a window and ``(start, end, name,
    args)`` spans."""
    return {"ops": {"/device:TPU:0": [(10, 20)]},
            "spans": [(window[0], window[1], "bench.window", {})] + spans_}


@pytest.fixture
def read_events(monkeypatch):
    def read(ev):
        monkeypatch.setattr(spans, "newest_xplane", lambda root: "x.pb")
        monkeypatch.setattr(spans, "load", lambda path: ev)
        return harness.load_metric(METRIC)({"trace": {"busy_s": 1.0}})
    return read


def test_join_rows_over_derived_facts(read_events):
    ev = events([
        (5, 40, "hf.load", {"facts": 500}),
        (6, 30, "hf.write", {"type": "edge", "rows_fresh": 500}),  # loaded
        (50, 400, "hf.infer", {"infer": 1}),
        (60, 70, "hf.join", {"rows": 500, "rows_out": 500}),
        (71, 90, "hf.join", {"rows": 800, "rows_out": 2500}),
        (100, 150, "hf.write", {"type": "sg", "rows_in": 2500,
                                "rows_fresh": 1000}),
        (160, 170, "hf.join", {"rows": 90, "rows_out": 3000}),
        (180, 190, "hf.write", {"type": "sg", "rows_fresh": 0}),
        (200, 250, "hf.write", {"type": "sg", "rows_fresh": 500}),
        (1100, 1200, "hf.join", {"rows_out": 10 ** 6}),  # after the window
    ])
    assert read_events(ev) == pytest.approx(6000 / 1500)


def test_join_spans_without_rows_out_read_nothing(read_events):
    # the parent program's join spans carry only ``rows``
    ev = events([(50, 400, "hf.infer", {}),
                 (60, 70, "hf.join", {"rows": 500}),
                 (100, 150, "hf.write", {"rows_fresh": 10})])
    assert read_events(ev) is None
    assert read_events(events([])) is None
    assert read_events({"ops": {}, "spans": []}) is None


def test_nothing_derived_reads_nothing(read_events):
    ev = events([(50, 400, "hf.infer", {}),
                 (60, 70, "hf.join", {"rows_out": 5}),
                 (100, 150, "hf.write", {"rows_fresh": 0})])
    assert read_events(ev) is None


def test_untraced_run_reads_nothing():
    read = harness.load_metric(METRIC)
    assert read({"trace": None, "units": 3}) is None
    assert read({}) is None
