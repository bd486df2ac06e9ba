"""One run of one benchmark cell: set-up, measured window, check, result.

Everything that belongs to a cell is found by name: the cell in
``BENCHMARK.json``, its configuration file, its traffic mix
``bench/traffic/<mix>.json`` (whose ``"loop"`` names the window loop in
``bench/loops/``), the configuration's generator in
``bench/generators/`` and each per-layer metric's reader
``bench/metrics/<metric>.py``.  Adding a cell, a mix or a metric adds
files and entries; it edits none.

The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``busy_s``/``window_s`` and ``breakdown`` with ``--trace 1``) and,
last, ``checks``: every number compared with its limit.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".bench_out")


class SpecError(Exception):
    """The cell, its files or its device cannot be found."""


# ---------------------------------------------------------------------------
# the cell and its files


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list
    root: str = ROOT


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, root: str = ROOT) -> Cell:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SpecError(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have: {', '.join(sorted(cells))})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_file = os.path.join(root, configs[w["config"]]["file"])
    traffic_file = os.path.join(root, "bench", "traffic",
                                w["traffic"] + ".json")
    for p in (cfg_file, traffic_file):
        if not os.path.exists(p):
            raise SpecError(f"missing {os.path.relpath(p, root)}")
    with open(cfg_file) as f:
        config = json.load(f)
    with open(traffic_file) as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer,
                root)


def load_metric(name: str, root: str = ROOT):
    """The reader of per-layer metric ``name``: ``read(ctx) -> float |
    None`` in ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def generator(config: dict):
    return importlib.import_module(f"bench.generators.{config['generator']}")


def loop_module(traffic: dict):
    return importlib.import_module(f"bench.loops.{traffic['loop']}")


# ---------------------------------------------------------------------------
# the program under test


def make_engine(config: dict, overrides: "dict | None" = None):
    """A fresh engine of the configuration, with its rules.
    ``overrides`` replaces engine options (the controls use it)."""
    from repro.core import EngineConfig, HiperfactEngine
    from repro.core.conditions import AddAction, Rule, cond, term
    e = config["engine"]
    ec = dataclasses.replace(getattr(EngineConfig, e["preset"])(e["backend"]),
                             **{**e.get("options", {}), **(overrides or {})})
    engine = HiperfactEngine(ec)
    engine.add_rules([
        Rule(r["name"], tuple(cond(*a) for a in r["if"]),
             tuple(AddAction(a[0], term(a[1]), term(a[2]), term(a[3]))
                   for a in r["then"]))
        for r in config["rules"]])
    return engine


def engine_facts(engine, vocab) -> tuple:
    """The engine's alive facts as ``{ftype: packed term-id keys}``
    (sorted, duplicates kept), and the count of alive facts it holds
    that the benchmark's vocabulary cannot name."""
    import numpy as np

    from bench.reference import pack
    strings = engine.store.strings
    to_vocab = np.fromiter(
        (vocab.index.get(strings.lookup_id(i), -1)
         for i in range(len(strings))), np.int64)
    out, unknown = {}, 0
    for ftype, tab in engine.store.tables.items():
        alive = np.flatnonzero(tab.alive)
        if len(alive) == 0:
            continue
        cols = np.stack([to_vocab[tab.ids[alive]], to_vocab[tab.attrs[alive]],
                         to_vocab[tab.vals[alive]]], axis=1)
        ok = (cols >= 0).all(axis=1) & (tab.valtypes[alive] == 0)
        unknown += int((~ok).sum())
        out[ftype] = np.sort(pack(cols[ok]))
    return out, unknown


def compare_facts(got: dict, unknown: int, want: dict) -> dict:
    """Missing, extra and duplicated facts of ``got`` against ``want``
    (both ``{ftype: sorted packed keys}``)."""
    import numpy as np
    missing = extra = dup = 0
    for ftype in set(got) | set(want):
        g = got.get(ftype, np.zeros(0, np.int64))
        w = want.get(ftype, np.zeros(0, np.int64))
        ug = np.unique(g)
        dup += len(g) - len(ug)
        missing += len(np.setdiff1d(w, ug, assume_unique=True))
        extra += len(np.setdiff1d(ug, w, assume_unique=True))
    return {"missing_facts": missing, "extra_facts": extra + unknown,
            "duplicate_facts": dup}


def alive_facts(engine) -> int:
    return sum(int(t.alive.sum()) for t in engine.store.tables.values())


# ---------------------------------------------------------------------------
# clocks, spans, compile accounting, trace


class CompileMeter:
    """Backend compile seconds and persistent-cache hits and misses,
    from JAX's own monitoring events."""

    def __init__(self) -> None:
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self) -> None:
        from jax import monitoring

        def on_duration(event: str, secs: float, **_) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs
                self.compiles += 1

        def on_event(event: str, **_) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def stats(self) -> dict:
        return {"backend_compile_s": self.compile_s,
                "programs": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def span(name: str):
    """A host span in the profiler's trace (``bench.load``, ...)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """Traces one part of the window, in a run with ``--trace 1``."""

    def __init__(self, on: bool, cell: str) -> None:
        self.on = on
        self.dir = os.path.join(OUT, "trace", cell)
        self.state = "idle"
        self._span = None

    def start(self) -> None:
        if not self.on or self.state != "idle":
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = span("bench.window")
        self._span.__enter__()
        self.state = "tracing"

    def stop(self) -> None:
        if self.state != "tracing":
            return
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def reduce(self) -> "dict | None":
        if self.state != "done":
            return None
        from bench import trace
        return trace.reduce(trace.find_xplane(self.dir))


def device_sync() -> None:
    """Wait until the device has run everything enqueued before."""
    import jax
    import jax.numpy as jnp
    jnp.zeros((), jnp.int32).block_until_ready()
    jax.effects_barrier()


def device_info(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_stats(dev) -> dict:
    return dev.memory_stats() or {}


def device_of(engine):
    import jax
    return getattr(engine.ops, "device", None) or jax.devices()[0]


# ---------------------------------------------------------------------------
# the run


class Run:
    """What a loop is handed: the cell, the seed and the tracer.
    ``phases`` holds set-up seconds by phase."""

    def __init__(self, cell: Cell, seed: int, tracer: Tracer,
                 overrides: "dict | None" = None,
                 control: "dict | None" = None) -> None:
        self.cell = cell
        self.overrides = overrides or {}  # engine options
        self.control = control or {}      # the loop's knobs
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.tracer = tracer
        self.phases: dict = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = (self.phases.get(name, 0.0)
                                 + time.perf_counter() - t0)


def _emit_checks(checks: dict) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devs, meter: CompileMeter,
             control: bool = False, phases: "dict | None" = None) -> dict:
    """Set up, measure, check; returns the result line's object.
    ``control`` switches on the loop's ``CONTROL``: the program's own
    path that breaks a guarantee the configuration states."""
    tracer = Tracer(trace, cell.name)
    mod = loop_module(cell.traffic)
    knobs = mod.CONTROL if control else {}
    run = Run(cell, seed, tracer, knobs.get("engine"), knobs.get("loop"))
    run.phases.update(phases or {})
    loop = mod.Loop(run)
    loop.setup()
    device_sync()
    setup_s = time.perf_counter() - t_start
    compile_setup = meter.stats()
    run.phases["compile"] = compile_setup
    print(json.dumps({"setup": {"setup_s": setup_s, **run.phases}}),
          file=sys.stderr, flush=True)

    gc.collect()
    gc.freeze()  # set-up's objects stay out of the window's collections
    res = loop.window(seconds)
    tracer.stop()
    compile_window = {k: v - compile_setup[k]
                      for k, v in meter.stats().items()}
    mem = memory_stats(devs[0])
    peak = mem.get("peak_bytes_in_use")
    held = loop.held_facts()
    checks = loop.check()
    gc.unfreeze()

    e2e = dict(res["end_to_end"])
    e2e["setup_s"] = setup_s
    if peak and held:
        e2e["hbm_bytes_per_fact"] = peak / held
    device = {**device_info(devs), "memory_peak_bytes": peak}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": res["attempted"], "failed": res["failed"]}
    ctx = dict(res["counters"])
    ctx["compile_setup"] = compile_setup
    ctx["compile_window"] = compile_window
    ctx["trace"] = tracer.reduce()
    print(json.dumps({"window": {"compile": compile_window,
                                 "held_facts": held, "memory": mem,
                                 "counters": _short(ctx)}}, default=str),
          file=sys.stderr, flush=True)
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = load_metric(m["name"], cell.root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        t = ctx["trace"]
        if t is not None:
            device["busy_s"] = t["busy_s"]
            device["window_s"] = t["window_s"]
            out["breakdown"] = {"device_ops": t["device_ops"][:10],
                                "idle_gaps": t["idle_gaps"][:10]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = checks
    _emit_checks(checks)
    return out


def _short(ctx: dict) -> dict:
    return {k: v for k, v in ctx.items()
            if not isinstance(v, list) or len(v) <= 16}


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start: "float | None" = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except SpecError as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s); nothing "
              f"was run", file=sys.stderr)
        return 2
    devs = devs[:cell.chips]
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()
    import repro.core  # noqa: F401  (the engine, imported before set-up)
    imported = {"import_s": time.perf_counter() - t_start}
    meter = CompileMeter()
    meter.install()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start,
                   devs, meter, phases=imported)
    print(json.dumps(out), flush=True)
    return 0
