"""The comparison that decides ``correct`` fails where it must: each
loop's control, and each fault a cell can have, planted in the program
underneath a whole run (the look for a chip skipped)."""

import pytest

from repro.core import HiperfactEngine, InferStats
from repro.serve import FactServer

CLOSURE = ["lubm-closure", "orb-tc-closure"]


def _failed(out, *names):
    assert not out["correct"], out["checks"]
    assert any(out["checks"][n]["value"] > out["checks"][n]["limit"]
               for n in names if n in out["checks"]), out["checks"]


@pytest.mark.parametrize("name", CLOSURE + ["lubm-stream", "lubm-serve"])
def test_control_is_not_correct(name, tiny, run_tiny):
    out = run_tiny(tiny(name), control=True)
    _failed(out, "missing_facts", "wrong_answers")


@pytest.fixture
def infer_after_setup(monkeypatch):
    """Plant ``fault`` into ``HiperfactEngine.infer`` once set-up is
    over: the window and the state it leaves are faulty."""
    def plant(fault):
        real = HiperfactEngine.infer
        calls = {"armed": False}

        def infer(self):
            if not calls["armed"]:
                return real(self)
            return fault(self, real)
        monkeypatch.setattr(HiperfactEngine, "infer", infer)
        return calls
    return plant


def _arm_after_setup(monkeypatch, calls):
    """Arm the fault where set-up ends: the harness freezes set-up's
    objects right before the window."""
    from bench import harness
    real = harness.gc.freeze

    def freeze():
        calls["armed"] = True
        real()
    monkeypatch.setattr(harness.gc, "freeze", freeze)


@pytest.mark.parametrize("name", CLOSURE + ["lubm-stream"])
def test_step_that_returns_state_unchanged(name, tiny, run_tiny,
                                           infer_after_setup, monkeypatch):
    calls = infer_after_setup(lambda self, real: InferStats())
    _arm_after_setup(monkeypatch, calls)
    _failed(run_tiny(tiny(name)), "missing_facts", "extra_facts")


@pytest.mark.parametrize("name", CLOSURE + ["lubm-stream"])
def test_half_of_the_batch_left_out(name, tiny, run_tiny, monkeypatch):
    real = HiperfactEngine.insert_facts
    calls = {"armed": False}

    def insert_facts(self, facts):
        if calls["armed"]:
            facts = list(facts)[: len(facts) // 2]
        return real(self, facts)
    monkeypatch.setattr(HiperfactEngine, "insert_facts", insert_facts)
    _arm_after_setup(monkeypatch, calls)
    _failed(run_tiny(tiny(name)), "missing_facts")


@pytest.mark.parametrize("name", CLOSURE + ["lubm-stream"])
def test_fact_altered_where_it_is_produced(name, tiny, run_tiny,
                                           infer_after_setup, monkeypatch):
    def altered(self, real):
        st = real(self)
        tab = max(self.store.tables.values(), key=lambda t: t.n)
        row = int(tab.alive.nonzero()[0][-1])
        tab.vals[row] = tab.ids[row]   # one fact now says something else
        return st
    calls = infer_after_setup(altered)
    _arm_after_setup(monkeypatch, calls)
    _failed(run_tiny(tiny(name)), "missing_facts", "extra_facts")


def test_served_answer_altered(tiny, run_tiny, monkeypatch):
    real = FactServer.serve
    calls = {"armed": False}

    def serve(self, conditions, tenant="default"):
        res = real(self, conditions, tenant)
        if calls["armed"] and res.rows:
            res.rows = res.rows[1:]  # one answer row dropped
        return res
    monkeypatch.setattr(FactServer, "serve", serve)
    _arm_after_setup(monkeypatch, calls)
    _failed(run_tiny(tiny("lubm-serve"), seconds=2.0), "wrong_answers")


def test_served_write_not_applied(tiny, run_tiny, monkeypatch):
    """A write that returns before it is applied: later reads miss it."""
    real = FactServer.append
    calls = {"armed": False}

    def append(self, facts, infer=None):
        if calls["armed"]:
            return 0
        return real(self, facts, infer)
    monkeypatch.setattr(FactServer, "append", append)
    _arm_after_setup(monkeypatch, calls)
    out = run_tiny(tiny("lubm-serve"), seconds=2.0)
    _failed(out, "stale_reads")
