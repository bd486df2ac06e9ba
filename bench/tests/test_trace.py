"""The trace reduction, on made-up events and on a small trace recorded
on a TPU v5e (``data/tiny.xplane.pb``: three jitted sorts of 2**16
lanes inside ``bench.infer``, a 10 ms sleep and a jitted sum inside
``bench.load``, a 5 ms sleep after them, all inside ``bench.window``)."""

import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


def test_union_clips_and_merges():
    assert trace.union([(5, 20, "a"), (10, 30, "b"), (40, 45, "c")],
                       0, 42) == [[5, 30], [40, 42]]


def test_reduce_events_by_hand():
    ev = {"spans": [(0, 100, "bench.window"), (0, 40, "bench.infer"),
                    (40, 100, "bench.load")],
          "ops": {"/device:TPU:0": [(10, 20, "fusion"), (15, 30, "sort"),
                                    (50, 60, "fusion")]},
          "programs": {"/device:TPU:0": [(10, 30, "jit__xla_sort"),
                                         (50, 60, "jit_f")]}}
    r = trace.reduce_events(ev)
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["idle_share"] == pytest.approx(0.7)
    assert r["idle_gaps"][0] == ["bench.load", pytest.approx(40e-9)]
    assert r["idle_by_span"] == {"bench.infer": pytest.approx(10e-9),
                                 "bench.load": pytest.approx(60e-9)}
    assert r["device_ops"][0] == ["jit__xla_sort", pytest.approx(20e-9)]


def test_no_device_plane_gives_nothing():
    assert trace.reduce_events({"spans": [(0, 1, "bench.window")],
                                "ops": {}, "programs": {}}) is None


def test_recorded_tpu_trace():
    ev = trace.load(DATA)
    assert any(p.startswith("/device:TPU") for p in ev["ops"])
    r = trace.reduce_events(ev)
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_share"] < 1
    names = {n for n, _ in r["idle_gaps"]}
    assert names <= {"bench.infer", "bench.load", trace.OUTSIDE}
    assert "bench.load" in names  # the sleep
    assert r["device_ops"][0][0].startswith("jit_")
    assert any(" sort(" in name for plane in ev["ops"].values()
               for *_, name in plane)
