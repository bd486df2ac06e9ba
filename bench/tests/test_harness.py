"""The harness: every cell rehearsed on the CPU, the refusal without a
chip, and cells, mixes and metrics found by name."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.generators import random_dag, univbench
from bench.reference import Reference

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]] + ["lubm-stream", "lubm-serve"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(name, tiny, run_tiny):
    cell = tiny(name)
    out = run_tiny(cell, seconds=1.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    want = {m["name"] for m in cell.end_to_end} - {"hbm_bytes_per_fact"}
    assert want <= set(out["metrics"]), out["metrics"]
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name,counted", [
    ("orb-tc-closure", {"fixpoint.rounds.closure", "joins.emit_ratio.closure",
                        "cache.hit_rate.closure", "compile.programs",
                        "backend.kernel_calls.closure"}),
    ("lubm-stream", {"fixpoint.passes_per_round.stream",
                     "fixpoint.full_evals.stream",
                     "h2d_bytes_per_fact.stream"}),
    ("lubm-serve", {"serve.locked_read_share", "serve.probes_per_call",
                    "loadgen.late_p95_ms"})])
def test_traced_run_reads_per_layer_metrics(name, counted, tiny, run_tiny):
    out = run_tiny(tiny(name), seconds=0.5, trace=True)
    assert out["correct"]
    got = set(out["metrics"])
    # counters read on the CPU too; trace numbers need a device plane
    assert counted <= got, got
    assert not any("idle_share" in m or "sort_share" in m for m in got)


def _bench_run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "orb-tc-closure",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_exits_nonzero_and_prints_nothing():
    res = _bench_run(ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "TPU" in res.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _bench_run(tmp_path)
    assert res.returncode != 0
    assert res.stdout.strip() == ""


def test_new_config_mix_and_metric_found_by_name(tmp_path, run_tiny):
    """A later PR adds a cell by adding files and entries only."""
    from conftest import shrink
    spec = json.loads(json.dumps(SPEC))
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "bench/configs/orb-tc.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "orb-tc-sparse"
    cfg["scale"] = {"nodes": 300, "edges": 900}
    (tmp_path / "bench/configs/orb-tc-sparse.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/closure-twice.json").write_text(
        json.dumps({"loop": "closure", "trace_units": 2}))
    (tmp_path / "bench/metrics/tiny.units.py").write_text(
        "def read(ctx):\n    return ctx.get('units')\n")
    spec["configs"].append({"name": "orb-tc-sparse", "source": "test",
                            "file": "bench/configs/orb-tc-sparse.json",
                            "reduced": ["nodes", "edges"], "why": "test"})
    spec["workloads"].append({"name": "tc-sparse", "config": "orb-tc-sparse",
                              "traffic": "closure-twice", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "tiny.units", "unit": "units",
                              "better": "higher", "source": "program_counter",
                              "layer": "harness", "moves": "closure_s",
                              "workloads": ["tc-sparse"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("tc-sparse", str(tmp_path))
    assert cell.config["scale"] == {"nodes": 300, "edges": 900}
    assert cell.traffic["trace_units"] == 2
    assert [m["name"] for m in cell.per_layer][-1] == "tiny.units"
    shrink(cell)
    out = run_tiny(cell, seconds=0.3, trace=True)
    assert out["correct"]
    assert out["metrics"]["tiny.units"]["value"] >= 1


def test_missing_cell_is_refused():
    with pytest.raises(harness.SpecError):
        harness.load_cell("no-such-cell")


def test_config_rules_are_the_program_ruleset():
    from repro.core.rulesets import rdfs_plus_rules
    with open(os.path.join(ROOT, "bench/configs/lubm-rdfsplus.json")) as f:
        cfg = json.load(f)
    assert harness.make_engine(cfg).rules == rdfs_plus_rules()


@pytest.mark.parametrize("gen,path", [
    (univbench, "bench/configs/lubm-rdfsplus.json"),
    (random_dag, "bench/configs/orb-tc.json")])
def test_every_seed_makes_the_same_sizes(gen, path):
    with open(os.path.join(ROOT, path)) as f:
        cfg = json.load(f)
    if gen is univbench:
        cfg["scale"]["universities"] = 1
    a, b = gen.generate(cfg, 1), gen.generate(cfg, 2**31 + 7)
    assert {k: len(v) for k, v in a.facts.items()} == \
        {k: len(v) for k, v in b.facts.items()}
    assert not all(np.array_equal(a.facts[k], b.facts[k]) for k in a.facts)
    if gen is univbench:
        rng = np.random.default_rng(0)
        sizes = [[len(e["Data"]) for e in univbench.enrolments(
            ds, np.arange(8) % 3, rng)] for ds in (a, b)]
        assert sizes[0] == sizes[1]


def test_random_dag_is_acyclic_and_simple():
    cfg = {"scale": {"nodes": 60, "edges": 600}}
    ds = random_dag.generate(cfg, 99)
    e = ds.facts["edge"]
    assert len({(s, o) for s, _, o in e.tolist()}) == 600
    # node ids were interned in the random topological order, after "to"
    assert ds.vocab.terms[0] == "to"
    assert all(s < o for s, _, o in e.tolist())


def test_reference_labels_give_every_snapshot():
    terms = {}

    def tid(t):
        return terms.setdefault(t, len(terms))
    rules = [{"if": [["e", "?x", "to", "?y"]], "then": [["p", "?x", "to", "?y"]]},
             {"if": [["e", "?x", "to", "?y"], ["p", "?y", "to", "?z"]],
              "then": [["p", "?x", "to", "?z"]]}]
    ref = Reference(rules, tid)
    a, b, c, to = tid("a"), tid("b"), tid("c"), tid("to")
    ref.add({"e": np.array([[a, to, b], [b, to, c]])},
            {"e": np.array([0, 2])})
    assert ref.query([["p", a, to, "?z"]], 1) == {(("?z", b),)}
    assert ref.query([["p", a, to, "?z"]], 2) == {(("?z", b),), (("?z", c),)}
    assert ref.query([["p", "?x", to, c]], 2) == {(("?x", a),), (("?x", b),)}
