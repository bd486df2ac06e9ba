"""Back-to-back materializations from load.

Each materialization starts from a fresh engine: ``insert_facts`` of
the base facts, then ``infer()`` to the fixpoint.  Every engine takes
the backend instance the program shares per process, so between
materializations the old engine is dropped and that instance's device
cache is cleared.  Set-up generates the facts and runs one
materialization, which compiles or loads every program.  The window
ends on a materialization boundary; ``closure_s`` is the window over
the materializations completed.  The check compares the last one's
whole fact set with the reference's fixpoint.
"""

from __future__ import annotations

import gc
import time

from bench import harness
from bench.loops import common


# the control (bench/control.py): the program's own path that breaks a
# stated guarantee; it stops the fixpoint after two rounds
# (``EngineConfig.max_iterations``)
CONTROL = {"engine": {"max_iterations": 2}}


class Loop:
    def __init__(self, run) -> None:
        self.run = run
        self.engine = None
        self.bytes_in_use: list = []

    def setup(self) -> None:
        run = self.run
        with run.phase("generate"):
            self.ds = harness.generator(run.config).generate(run.config,
                                                              run.seed)
            self.facts = self.ds.fact_objects()
        t0 = time.perf_counter()
        st = self.materialize()
        run.phases["warm_materialization"] = time.perf_counter() - t0
        run.phases["base_facts"] = len(self.facts)
        run.phases["fixpoint_rounds"] = st.iterations

    def materialize(self):
        """One materialization; returns its ``InferStats``."""
        if self.engine is not None:
            ops = self.engine.ops
            self.engine = None
            ops.cache.clear()
            gc.collect()
        engine = harness.make_engine(self.run.config, self.run.overrides)
        with harness.span("bench.load"):
            engine.insert_facts(self.facts)
        with harness.span("bench.infer"):
            st = engine.infer()
        harness.device_sync()
        self.engine = engine
        return st

    def window(self, seconds: float) -> dict:
        run = self.run
        units = run.traffic.get("trace_units", 1)
        infer: dict = {}
        ops: dict = {}
        n = 0
        t0 = time.perf_counter()
        while True:
            if n == 0:
                run.tracer.start()
            before = common.ops_snapshot(self.engine.ops)
            st = self.materialize()
            common.add(ops, common.ops_delta(
                common.ops_snapshot(self.engine.ops), before))
            common.add_infer(infer, st)
            n += 1
            if n == units:
                run.tracer.stop()
            mem = harness.memory_stats(harness.device_of(self.engine))
            self.bytes_in_use.append(mem.get("bytes_in_use"))
            if time.perf_counter() - t0 >= seconds:
                break
        dt = time.perf_counter() - t0
        return {"end_to_end": {"closure_s": dt / n},
                "attempted": n, "failed": 0,
                "counters": {"units": n, "infer": infer, "ops": ops,
                             "bytes_in_use": self.bytes_in_use}}

    def held_facts(self) -> int:
        return harness.alive_facts(self.engine)

    def check(self) -> dict:
        from bench.reference import Reference
        got, unknown = harness.engine_facts(self.engine, self.ds.vocab)
        ops = self.engine.ops
        self.engine = None
        ops.cache.clear()
        gc.collect()
        ref = Reference(self.run.config["rules"], self.ds.vocab.id)
        ref.add(self.ds.facts)
        cmp = harness.compare_facts(got, unknown, ref.facts())
        return {k: {"value": v, "limit": 0} for k, v in cmp.items()}
