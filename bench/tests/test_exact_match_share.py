"""``write.exact_match_share.closure``: the share of the counting write
side's rows looked up on the device, from the ``hf.write`` spans'
arguments, on made-up events."""

import pytest

from bench import harness, spans

METRIC = "write.exact_match_share.closure"


def events(writes, window=(0, 100)):
    """``spans.load``-shaped events: a window and ``hf.write`` spans
    ``(start, args)``."""
    return {"ops": {"/device:TPU:0": [(10, 20)]},
            "spans": [(window[0], window[1], "bench.window", {})] + [
                (s, s + 5, "hf.write", args) for s, args in writes]}


@pytest.fixture
def read_events(monkeypatch):
    def read(ev):
        monkeypatch.setattr(spans, "newest_xplane", lambda root: "x.pb")
        monkeypatch.setattr(spans, "load", lambda path: ev)
        return harness.load_metric(METRIC)({"trace": {"busy_s": 1.0}})
    return read


def test_all_rows_on_the_device(read_events):
    ev = events([(10, {"type": "path", "match_rows": 31000,
                       "match_host": 0}),
                 (30, {"type": "path", "rows_fresh": 5}),  # set path
                 (50, {"type": "path", "match_rows": 12, "match_host": 0})])
    assert read_events(ev) == pytest.approx(100.0)


def test_mixed_share_counts_only_the_window(read_events):
    ev = events([(10, {"match_rows": 30, "match_host": 10}),
                 (60, {"match_rows": 0, "match_host": 60}),
                 (150, {"match_rows": 900, "match_host": 0})])
    assert read_events(ev) == pytest.approx(30.0)


def test_spans_without_the_arguments_read_nothing(read_events):
    # the parent program's write spans carry no lookup arguments
    assert read_events(events([(10, {"type": "path", "rows_in": 4})])) is None
    assert read_events(events([])) is None
    assert read_events({"ops": {}, "spans": []}) is None


def test_untraced_run_reads_nothing():
    read = harness.load_metric(METRIC)
    assert read({"trace": None, "units": 3}) is None
    assert read({}) is None
