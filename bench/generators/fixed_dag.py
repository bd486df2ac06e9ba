"""One random DAG of the tc test's family, the same graph in every run.

OpenRuleBench ships its graphs as fixed data files.  The edges here are
``random_dag``'s for the configuration's ``graph_seed``, so every run
closes the same graph and derives relations of the same size.  The
run's seed renames the nodes and shuffles the order the edges are
loaded in, which changes the term ids the engine assigns and so the
orders its sorts and joins see.
"""

from __future__ import annotations

import numpy as np

from bench.dataset import Dataset, Vocab
from bench.generators import random_dag


def generate(config: dict, seed: int) -> Dataset:
    graph = random_dag.generate(config, config["graph_seed"])
    rng = np.random.default_rng([abs(int(seed)), 3])
    old = graph.vocab
    vocab = Vocab()
    to = vocab.id("to")
    nodes = np.array([i for i, t in enumerate(old.terms) if t != "to"])
    renamed = np.empty(len(old), np.int64)
    renamed[old.index["to"]] = to
    renamed[nodes] = vocab.ids(f"n{i}" for i in rng.permutation(len(nodes)))
    edges = renamed[graph.facts["edge"]]
    return Dataset(vocab, {"edge": edges[rng.permutation(len(edges))]})
