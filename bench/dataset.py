"""Facts as the benchmark makes them: term ids over one vocabulary.

A ``Dataset`` holds every fact type as an ``(n, 3)`` int64 array of
``(id, attr, val)`` term ids; ``vocab`` maps a term id to its string.
The engine is given ``Fact`` objects built from the strings, and the
reference works on the ids, so the two share nothing but the strings.
"""

from __future__ import annotations

import dataclasses

import numpy as np


class Vocab:
    """Term strings <-> dense ids, in the order they were first seen."""

    def __init__(self) -> None:
        self.terms: list[str] = []
        self.index: dict[str, int] = {}

    def __len__(self) -> int:
        return len(self.terms)

    def id(self, term: str) -> int:
        i = self.index.get(term)
        if i is None:
            i = self.index[term] = len(self.terms)
            self.terms.append(term)
        return i

    def ids(self, terms) -> np.ndarray:
        return np.fromiter((self.id(t) for t in terms), np.int64)


@dataclasses.dataclass
class Dataset:
    vocab: Vocab
    facts: dict          # fact type -> (n, 3) int64 term ids
    extra: dict = dataclasses.field(default_factory=dict)

    def fact_objects(self, facts: "dict | None" = None) -> list:
        """``facts`` (default: the whole dataset) as engine ``Fact``s."""
        from repro.core import Fact
        terms = self.vocab.terms
        out = []
        for ftype, arr in (self.facts if facts is None else facts).items():
            out += [Fact(ftype, terms[s], terms[p], terms[o])
                    for s, p, o in arr.tolist()]
        return out


def triples(vocab: Vocab, rows) -> np.ndarray:
    """``[(id, attr, val), ...]`` strings as an ``(n, 3)`` id array."""
    flat = vocab.ids(t for row in rows for t in row)
    return flat.reshape(-1, 3)


def cat(parts: dict) -> dict:
    """``{ftype: [arrays]}`` -> ``{ftype: one (n, 3) array}``."""
    return {t: (np.concatenate(a) if a else np.zeros((0, 3), np.int64))
            for t, a in parts.items()}
