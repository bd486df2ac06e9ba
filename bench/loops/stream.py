"""Streaming rounds over a closed store, one writer, back to back.

Each round appends ``enrolments_per_round`` new enrolments, expires the
enrolments appended ``expire_after_rounds`` rounds earlier, and runs
``infer()``.  Set-up loads and closes the base facts, then runs
``expire_after_rounds + warm_rounds`` rounds, so every measured round
both appends and deletes.  ``reinfer_ms`` is the window over the rounds
completed.  The check compares the fact set after the window's last
round with the reference's fixpoint of the facts then asserted.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from bench import harness
from bench.loops import common


# the control (bench/control.py): the program's own path that breaks a
# stated guarantee; it stops each round's fixpoint after two
# rounds (``EngineConfig.max_iterations``)
CONTROL = {"engine": {"max_iterations": 2}}


class Loop:
    def __init__(self, run) -> None:
        self.run = run
        self.rounds: collections.deque = collections.deque()
        self.rng = np.random.default_rng([abs(int(run.seed)), 3])

    def setup(self) -> None:
        run, tr = self.run, self.run.traffic
        gen = harness.generator(run.config)
        with run.phase("generate"):
            self.ds = gen.generate(run.config, run.seed)
            facts = self.ds.fact_objects()
        self.engine = harness.make_engine(run.config, run.overrides)
        with run.phase("load"):
            self.engine.insert_facts(facts)
        with run.phase("warm_infer"):
            self.engine.infer()
            harness.device_sync()
        with run.phase("warm_rounds"):
            for _ in range(tr["expire_after_rounds"] + tr["warm_rounds"]):
                self.round()
            harness.device_sync()

    def round(self):
        """One round; returns (``InferStats``, facts written)."""
        tr = self.run.traffic
        gen = harness.generator(self.run.config)
        depts = self.rng.integers(0, len(self.ds.extra["depts"]),
                                  tr["enrolments_per_round"])
        batch = np.concatenate([e["Data"] for e in
                                gen.enrolments(self.ds, depts, self.rng)])
        self.rounds.append(batch)
        written = len(batch)
        with harness.span("bench.append"):
            self.engine.insert_facts(self.ds.fact_objects({"Data": batch}))
        if len(self.rounds) > tr["expire_after_rounds"]:
            old = self.rounds.popleft()
            written += len(old)
            with harness.span("bench.expire"):
                self.engine.delete_facts(self.ds.fact_objects({"Data": old}))
        with harness.span("bench.infer"):
            st = self.engine.infer()
        harness.device_sync()
        return st, written

    def window(self, seconds: float) -> dict:
        run = self.run
        units = run.traffic.get("trace_units", 3)
        infer: dict = {}
        before = common.ops_snapshot(self.engine.ops)
        n = written = 0
        t0 = time.perf_counter()
        while True:
            if n == 0:
                run.tracer.start()
            st, w = self.round()
            common.add_infer(infer, st)
            written += w
            n += 1
            if n == units:
                run.tracer.stop()
            if time.perf_counter() - t0 >= seconds:
                break
        dt = time.perf_counter() - t0
        ops = common.ops_delta(common.ops_snapshot(self.engine.ops), before)
        return {"end_to_end": {"reinfer_ms": 1000.0 * dt / n},
                "attempted": n, "failed": 0,
                "counters": {"units": n, "infer": infer, "ops": ops,
                             "facts_written": written}}

    def held_facts(self) -> int:
        return harness.alive_facts(self.engine)

    def check(self) -> dict:
        from bench.reference import Reference
        got, unknown = harness.engine_facts(self.engine, self.ds.vocab)
        ops = self.engine.ops
        self.engine = None
        ops.cache.clear()
        asserted = dict(self.ds.facts)
        asserted["Data"] = np.concatenate([asserted["Data"], *self.rounds])
        ref = Reference(self.run.config["rules"], self.ds.vocab.id)
        ref.add(asserted)
        cmp = harness.compare_facts(got, unknown, ref.facts())
        return {k: {"value": v, "limit": 0} for k, v in cmp.items()}
