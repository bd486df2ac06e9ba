"""Open-loop reads and writes through ``FactServer`` over a closed store.

Requests arrive at ``rate_per_s`` on a schedule fixed before the
window: the inter-arrival gaps are the quantiles of an exponential
distribution, so every seed has the same gaps and the same count of
each kind, in its own order.  Reads are point probes
``(Data, <person>, type, ?t)`` (the batched, lock-free path) and
department queries ``memberOf <dept>`` and ``type Student`` (the
evaluation path, under the writer lock after a write).  Persons and
departments are drawn Zipf over a popularity order that is the same
for every seed.  A write appends one enrolment with ``infer=True``.
Set-up loads and closes the base facts and runs the same mix for
``warm_seconds`` at ``warm_rate_per_s``, above the window's rate, so
that the window meets shapes that set-up already compiled.

``read_p95_ms`` is the 95th percentile over every read due in the
window, timed from its due time to its answer.  The check replays the
server's write history on the reference and compares a seeded sample
of the window's reads, each at its own snapshot token; it also checks
that each read saw every write that returned before it was sent.
"""

from __future__ import annotations

import concurrent.futures
import threading
import time

import numpy as np

from bench import harness

HOT_STREAM = 0x407  # the popularity order of persons and departments

READ_ATOMS = {
    "probe": lambda who: [["Data", who, "type", "?t"]],
    "dept": lambda dept: [["Data", "?x", "memberOf", dept],
                          ["Data", "?x", "type", "Student"]],
}


def zipf_ranks(rng, n_keys: int, theta: float, size: int) -> np.ndarray:
    """YCSB-style Zipfian ranks in ``[0, n_keys)``."""
    w = 1.0 / np.arange(1, n_keys + 1) ** theta
    cdf = np.cumsum(w) / w.sum()
    return np.minimum(np.searchsorted(cdf, rng.random(size)), n_keys - 1)


# the control (bench/control.py): the program's own path that breaks a
# stated guarantee; it appends without re-inferring
# (``FactServer.append(infer=False)``)
CONTROL = {"loop": {"append_infer": False}}


class Loop:
    def __init__(self, run) -> None:
        self.run = run
        self.records: list = []
        self.writes_done: list = []   # completion times, in lock order
        self._lock = threading.Lock()

    # ----------------------------------------------------------- schedule
    def schedule(self, seconds: float, stream: int, rate: float) -> list:
        tr = self.run.traffic
        rng = np.random.default_rng([abs(int(self.run.seed)), stream])
        n = max(1, int(round(rate * seconds)))
        n_w = int(round(n * tr["write_share"]))
        n_probe = int(round((n - n_w) * tr["probe_share_of_reads"]))
        kinds = np.array(["write"] * n_w + ["probe"] * n_probe
                         + ["dept"] * (n - n_w - n_probe))
        rng.shuffle(kinds)
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps = rng.permutation(gaps) * (seconds / gaps.sum())
        due = np.cumsum(gaps) - gaps[0]
        persons = self.ds.extra["persons"]
        depts = self.ds.extra["depts"]
        # which keys are hot is the same for every seed (a fixed
        # stream), so seeds differ in order, not in the work
        hot = np.random.default_rng(HOT_STREAM)
        p_order = hot.permutation(len(persons))
        d_order = hot.permutation(len(depts))
        p_keys = p_order[zipf_ranks(rng, len(persons), tr["zipf_theta"], n)]
        d_keys = d_order[zipf_ranks(rng, len(depts), tr["zipf_theta"], n)]
        gen = harness.generator(self.run.config)
        out = []
        for i, kind in enumerate(kinds.tolist()):
            if kind == "write":
                (facts,) = gen.enrolments(self.ds, [d_keys[i]], rng)
                payload = self.ds.fact_objects(facts)
            elif kind == "probe":
                payload = READ_ATOMS["probe"](persons[p_keys[i]])
            else:
                payload = READ_ATOMS["dept"](depts[d_keys[i]]["name"])
            out.append((float(due[i]), kind, payload))
        return out

    # -------------------------------------------------------------- setup
    def setup(self) -> None:
        run, tr = self.run, self.run.traffic
        from repro.serve import FactServer
        with run.phase("generate"):
            self.ds = harness.generator(run.config).generate(run.config,
                                                              run.seed)
            facts = self.ds.fact_objects()
        self.engine = harness.make_engine(run.config, run.overrides)
        with run.phase("load"):
            self.engine.insert_facts(facts)
        with run.phase("warm_infer"):
            self.engine.infer()
            harness.device_sync()
        self.server = FactServer(self.engine,
                                 batch_window=tr["batch_window_s"],
                                 max_batch=tr["max_batch"],
                                 record_history=True)
        with run.phase("warm_traffic"):
            self.drive(self.schedule(tr["warm_seconds"], 5,
                                     tr["warm_rate_per_s"]), measured=False)
            harness.device_sync()

    # ------------------------------------------------------------ traffic
    def _request(self, kind: str, payload, due: float, t0: float,
                 measured: bool) -> None:
        from repro.core.conditions import cond
        sent = time.perf_counter()
        rec = {"kind": kind, "due": due, "late": sent - t0 - due}
        try:
            if kind == "write":
                with harness.span("bench.write"):
                    self.server.append(payload, infer=self.run.control.get(
                        "append_infer", True))
                with self._lock:
                    self.writes_done.append(time.perf_counter())
            else:
                with harness.span("bench.read"):
                    res = self.server.serve([cond(*a) for a in payload])
                rec.update(atoms=payload, rows=res.rows, token=res.token,
                           sent=sent)
        except Exception as exc:  # a failed request counts as failed
            rec["error"] = repr(exc)
        rec["done"] = time.perf_counter() - t0
        if measured:
            with self._lock:
                self.records.append(rec)

    def drive(self, sched: list, measured: bool) -> None:
        """Send ``sched`` open loop and wait for every request."""
        tr = self.run.traffic
        pool = concurrent.futures.ThreadPoolExecutor(tr["threads"])
        t0 = time.perf_counter()
        futs = []
        try:
            for due, kind, payload in sched:
                wait = t0 + due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                futs.append(pool.submit(self._request, kind, payload, due,
                                        t0, measured))
                if measured and self.run.tracer.state == "idle":
                    self.run.tracer.start()
                if (measured and self.run.tracer.state == "tracing"
                        and due >= tr["trace_seconds"]):
                    self.run.tracer.stop()
            done, pending = concurrent.futures.wait(
                futs, timeout=tr["drain_s"])
            for f in done:
                f.result()
        finally:
            pool.shutdown(wait=True, cancel_futures=True)

    # ------------------------------------------------------------- window
    def window(self, seconds: float) -> dict:
        sched = self.schedule(seconds, 4, self.run.traffic["rate_per_s"])
        before = self.server.stats()
        self.drive(sched, measured=True)
        self.run.tracer.stop()
        after = self.server.stats()
        reads = [r for r in self.records if r["kind"] != "write"]
        ok = [r for r in reads if "error" not in r]
        lat = np.array([r["done"] - r["due"] for r in ok]) * 1000.0
        late = [r["late"] * 1000.0 for r in self.records]
        served = {k: after["served"][k] - before["served"].get(k, 0)
                  for k in after["served"]}
        batch = {k: after["batch"][k] - before["batch"][k]
                 for k in ("device_calls", "batched_queries")}
        half = seconds / 2
        first = [r["done"] - r["due"] for r in ok if r["due"] < half]
        second = [r["done"] - r["due"] for r in ok if r["due"] >= half]
        counters = {
            "reads": len(reads), "served": served, "batch": batch,
            "late_ms": late,
            "writes": after["writes"] - before["writes"],
            "read_p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
            "read_max_ms": float(lat.max()) if len(lat) else None,
            "read_p95_first_half_ms": 1000 * float(np.percentile(first, 95))
            if first else None,
            "read_p95_second_half_ms": 1000 * float(np.percentile(second, 95))
            if second else None,
        }
        e2e = {}
        if len(lat) == len(reads) and len(lat):
            e2e["read_p95_ms"] = float(np.percentile(lat, 95))
        failed = sum(1 for r in self.records if "error" in r)
        return {"end_to_end": e2e, "attempted": len(self.records),
                "failed": failed, "counters": counters}

    def held_facts(self) -> int:
        return harness.alive_facts(self.engine)

    # -------------------------------------------------------------- check
    def check(self) -> dict:
        from bench.reference import Reference
        self.server.close()
        vocab = self.ds.vocab
        tok = {}
        facts = [self.ds.facts["Data"]]
        labels = [np.zeros(len(self.ds.facts["Data"]), np.int64)]
        for k, (kind, fs, token) in enumerate(self.server.history):
            tok.setdefault(token, k)
            if kind == "append":
                ids = np.array([[vocab.index[f.id], vocab.index[f.attr],
                                 vocab.index[f.val]] for f in fs], np.int64)
                facts.append(ids)
                labels.append(np.full(len(ids), k, np.int64))
        done = np.array(sorted(self.writes_done))
        reads = [r for r in self.records
                 if r["kind"] != "write" and "error" not in r]
        rng = np.random.default_rng([abs(int(self.run.seed)), 6])
        n = min(len(reads), self.run.traffic["check_reads"])
        sample = [reads[i] for i in sorted(rng.choice(len(reads), n,
                                                      replace=False))]
        self.server = self.engine = None
        ref = Reference(self.run.config["rules"], vocab.id)
        ref.add({"Schema": self.ds.facts["Schema"],
                 "Data": np.concatenate(facts)},
                {"Schema": np.zeros(len(self.ds.facts["Schema"]), np.int64),
                 "Data": np.concatenate(labels)})
        wrong = torn = stale = 0
        for r in sample:
            k = tok.get(r["token"])
            if k is None:
                torn += 1
                continue
            # writes are serialized: the m that returned before this
            # read was sent are the first m of the history
            if k < int(np.searchsorted(done, r["sent"], "right")):
                stale += 1
            atoms = [[a[0]] + [t if t.startswith("?") else vocab.index.get(
                t, -1) for t in a[1:]] for a in r["atoms"]]
            want = ref.query(atoms, k)
            got = {tuple(sorted((f"?{v}", vocab.index.get(x, -1))
                                for v, x in row.items()))
                   for row in r["rows"]}
            wrong += got != want or len(got) != len(r["rows"])
        return {"wrong_answers": {"value": wrong, "limit": 0},
                "torn_reads": {"value": torn, "limit": 0},
                "stale_reads": {"value": stale, "limit": 0},
                "no_reads_checked": {"value": int(not sample), "limit": 0}}
