"""OpenRuleBench-style random acyclic graph for the tc recursion test.

``edges`` distinct node pairs drawn uniformly from all pairs and
oriented along a random node order, so the graph is acyclic.  The node
and edge counts are the same for every seed.
"""

from __future__ import annotations

import numpy as np

from bench.dataset import Dataset, Vocab


def generate(config: dict, seed: int) -> Dataset:
    n, m = config["scale"]["nodes"], config["scale"]["edges"]
    rng = np.random.default_rng([abs(int(seed)), 2])
    vocab = Vocab()
    to = vocab.id("to")
    names = vocab.ids(f"n{i}" for i in rng.permutation(n))  # topological
    # pair index -> (i, j), i < j, over the n(n-1)/2 pairs
    picks = rng.choice(n * (n - 1) // 2, size=m, replace=False)
    i = (n - 2 - np.floor(np.sqrt(-8 * picks + 4 * n * (n - 1) - 7) / 2
                          - 0.5)).astype(np.int64)
    j = picks + i + 1 - n * (n - 1) // 2 + (n - i) * ((n - i) - 1) // 2
    edges = np.stack([names[i], np.full(m, to), names[j]], axis=1)
    return Dataset(vocab, {"edge": edges.astype(np.int64)})
