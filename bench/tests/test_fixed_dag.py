"""The ``fixed_dag`` generator of the sg cell: one graph in every run,
renamed and reordered by the run's seed."""

import json
import os

import numpy as np

from bench import harness
from bench.generators import fixed_dag
from bench.reference import Reference

ROOT = harness.ROOT


def _config():
    with open(os.path.join(ROOT, "bench/configs/orb-sg.json")) as f:
        return json.load(f)


def test_every_seed_makes_the_same_sizes():
    cfg = _config()
    a, b = fixed_dag.generate(cfg, 1), fixed_dag.generate(cfg, 2**31 + 7)
    assert {k: len(v) for k, v in a.facts.items()} == \
        {k: len(v) for k, v in b.facts.items()}
    assert not all(np.array_equal(a.facts[k], b.facts[k]) for k in a.facts)


def test_fixed_dag_is_one_graph_renamed():
    """Every seed loads the same graph under other names and in another
    order, so the derived relation has the same size."""
    cfg = _config()
    cfg["scale"] = {"nodes": 80, "edges": 400}
    sizes, degrees = set(), set()
    for seed in (1, 2**31 + 7):
        ds = fixed_dag.generate(cfg, seed)
        e = ds.facts["edge"]
        degrees.add(tuple(sorted(np.bincount(e[:, 0]).tolist())))
        ref = Reference(cfg["rules"], ds.vocab.id)
        ref.add(ds.facts)
        sizes.add(len(ref.facts()["sg"]))
    assert len(sizes) == len(degrees) == 1
    assert not np.array_equal(fixed_dag.generate(cfg, 1).facts["edge"], e)
