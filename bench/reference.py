"""Plain reference: semi-naive Datalog over term-id triples, in numpy.

It imports nothing of the program.  Rules come from the configuration
file (``"if"``/``"then"`` atoms of ``[fact type, id, attr, val]`` with
``?x`` variables), facts from the benchmark's own generator.

Every fact carries a label: the least write index at which it holds.
Base facts have label 0 and the facts of write ``k`` label ``k``; a
derivation's label is the largest label of its premises, and a fact
keeps the least over its derivations.  So the fact set after ``k``
writes is exactly the facts with label ``<= k``, and one fixpoint gives
the answer at every snapshot a served read can carry.  Without writes
every label is 0 and this is the plain least fixpoint.
"""

from __future__ import annotations

import numpy as np

BITS = 21                 # term ids per slot; three slots pack into int64
MASK = (1 << BITS) - 1


def pack(cols: np.ndarray) -> np.ndarray:
    c = cols.astype(np.int64)
    return (c[:, 0] << (2 * BITS)) | (c[:, 1] << BITS) | c[:, 2]


def unpack(keys: np.ndarray) -> np.ndarray:
    return np.stack([(keys >> (2 * BITS)) & MASK, (keys >> BITS) & MASK,
                     keys & MASK], axis=1)


def _pack_vals(cols: list) -> np.ndarray:
    """Join key over up to three term-id columns."""
    key = np.zeros(len(cols[0]) if cols else 0, np.int64)
    for c in cols:
        key = (key << BITS) | c.astype(np.int64)
    return key


class Relation:
    """One fact type: sorted unique packed keys with their labels."""

    def __init__(self) -> None:
        self.keys = np.zeros(0, np.int64)
        self.labels = np.zeros(0, np.int64)
        self._cols = None
        self._by_po = None

    def select(self, slots: list) -> np.ndarray:
        """Row positions that can match ``slots``' constants: a range of
        the (id, attr, val) order when the id is given, of an (attr,
        val, id) order when both of those are, else every row."""
        s, p, o = (None if _is_var(t) else t for t in slots)
        if s is not None:
            keys, perm, lead, rest = self.keys, None, s, p
        elif p is not None and o is not None:
            if self._by_po is None:
                k2 = pack(self.cols()[:, [1, 2, 0]])
                perm = np.argsort(k2, kind="stable")
                self._by_po = (k2[perm], perm)
            (keys, perm), lead, rest = self._by_po, p, o
        else:
            return np.arange(len(self.keys))
        lo = lead << (2 * BITS)
        hi = lo | (MASK << BITS) | MASK
        if rest is not None:
            lo |= rest << BITS
            hi = lo | MASK
        rows = np.arange(np.searchsorted(keys, lo, "left"),
                         np.searchsorted(keys, hi, "right"))
        return rows if perm is None else perm[rows]

    def cols(self) -> np.ndarray:
        if self._cols is None:
            self._cols = unpack(self.keys)
        return self._cols

    def merge(self, keys: np.ndarray, labels: np.ndarray):
        """Fold candidates in; return the (keys, labels) that are new or
        improved — the next round's delta."""
        if len(keys) == 0:
            return keys, labels
        order = np.lexsort((labels, keys))
        keys, labels = keys[order], labels[order]
        first = np.ones(len(keys), bool)
        first[1:] = keys[1:] != keys[:-1]
        keys, labels = keys[first], labels[first]
        pos = np.searchsorted(self.keys, keys)
        hit = pos < len(self.keys)
        hit[hit] = self.keys[pos[hit]] == keys[hit]
        better = np.zeros(len(keys), bool)
        better[hit] = labels[hit] < self.labels[pos[hit]]
        if better.any():
            self.labels[pos[better]] = labels[better]
        new = ~hit
        if new.any():
            allk = np.concatenate([self.keys, keys[new]])
            alll = np.concatenate([self.labels, labels[new]])
            order = np.argsort(allk, kind="stable")
            self.keys, self.labels = allk[order], alll[order]
            self._cols = self._by_po = None
        changed = better | new
        return keys[changed], labels[changed]


def _is_var(t) -> bool:
    return isinstance(t, str) and t.startswith("?")


class Reference:
    """Least fixpoint of ``rules`` over labelled facts."""

    def __init__(self, rules: list, term_id) -> None:
        # rules: [{"if": [[ftype, s, p, o], ...], "then": [...]}]
        # term_id: constant string -> id (the benchmark's vocabulary)
        self.rules = []
        for r in rules:
            self.rules.append((
                [(a[0], [t if _is_var(t) else term_id(t) for t in a[1:]])
                 for a in r["if"]],
                [(a[0], [t if _is_var(t) else term_id(t) for t in a[1:]])
                 for a in r["then"]]))
        self.rel: dict[str, Relation] = {}
        self.rounds = 0

    # ------------------------------------------------------------ joins
    @staticmethod
    def _match(cols: np.ndarray, labels: np.ndarray, slots: list):
        """Rows of one atom: constants filtered, repeated variables
        equal.  Returns ({var: column}, labels)."""
        ok = np.ones(len(cols), bool)
        first: dict[str, int] = {}
        for i, t in enumerate(slots):
            if _is_var(t):
                if t in first:
                    ok &= cols[:, i] == cols[:, first[t]]
                else:
                    first[t] = i
            else:
                ok &= cols[:, i] == t
        return ({v: cols[ok, i] for v, i in first.items()}, labels[ok])

    @staticmethod
    def _join(b: dict, bl: np.ndarray, r: dict, rl: np.ndarray):
        shared = [v for v in r if v in b]
        if not shared:  # cross product (no rule here needs one)
            li = np.repeat(np.arange(len(bl)), len(rl))
            ri = np.tile(np.arange(len(rl)), len(bl))
        else:
            kb = _pack_vals([b[v] for v in shared])
            kr = _pack_vals([r[v] for v in shared])
            order = np.argsort(kr, kind="stable")
            kr = kr[order]
            lo = np.searchsorted(kr, kb, "left")
            hi = np.searchsorted(kr, kb, "right")
            cnt = hi - lo
            li = np.repeat(np.arange(len(kb)), cnt)
            start = np.repeat(lo - np.cumsum(cnt) + cnt, cnt)
            ri = order[start + np.arange(len(li))]
        out = {v: c[li] for v, c in b.items()}
        for v, c in r.items():
            if v not in out:
                out[v] = c[ri]
        return out, np.maximum(bl[li], rl[ri])

    def _atom_rows(self, ftype: str, slots: list, delta: "dict | None"):
        if delta is not None:
            keys, labels = delta.get(ftype, (None, None))
            if keys is None or len(keys) == 0:
                return None
            return self._match(unpack(keys), labels, slots)
        rel = self.rel.get(ftype)
        if rel is None or len(rel.keys) == 0:
            return None
        return self._match(rel.cols(), rel.labels, slots)

    def _fire(self, body: list, head: list, at: int, delta: dict, out: dict):
        first = self._atom_rows(body[at][0], body[at][1], delta)
        if first is None or len(first[1]) == 0:
            return
        b, bl = first
        rest = [i for i in range(len(body)) if i != at]
        while rest:
            # next: the atom sharing most variables (constants first)
            rest.sort(key=lambda i: -sum(
                1 for t in body[i][1] if (not _is_var(t)) or t in b))
            i = rest.pop(0)
            rows = self._atom_rows(body[i][0], body[i][1], None)
            if rows is None:
                return
            b, bl = self._join(b, bl, *rows)
            if len(bl) == 0:
                return
        for ftype, slots in head:
            cols = np.stack([b[t] if _is_var(t) else np.full(len(bl), t)
                             for t in slots], axis=1)
            out.setdefault(ftype, []).append((pack(cols), bl))

    # --------------------------------------------------------- fixpoint
    def add(self, facts: dict, labels: "dict | int" = 0) -> None:
        """Assert ``{ftype: (n, 3) ids}`` at ``labels`` (one label, or
        ``{ftype: labels per row}``) and run to the fixpoint."""
        delta = {}
        for ftype, arr in facts.items():
            arr = np.asarray(arr)
            lab = (np.asarray(labels[ftype], np.int64)
                   if isinstance(labels, dict)
                   else np.full(len(arr), labels, np.int64))
            rel = self.rel.setdefault(ftype, Relation())
            k, l = rel.merge(pack(arr), lab)
            if len(k):
                delta[ftype] = (k, l)
        while delta:
            self.rounds += 1
            out: dict = {}
            for body, head in self.rules:
                for at in range(len(body)):
                    self._fire(body, head, at, delta, out)
            delta = {}
            for ftype, parts in out.items():
                rel = self.rel.setdefault(ftype, Relation())
                k, l = rel.merge(np.concatenate([p[0] for p in parts]),
                                 np.concatenate([p[1] for p in parts]))
                if len(k):
                    delta[ftype] = (k, l)

    def facts(self, upto: "int | None" = None) -> dict:
        """``{ftype: sorted packed keys}`` of the facts with label
        ``<= upto`` (all of them by default)."""
        out = {}
        for ftype, rel in self.rel.items():
            keys = rel.keys if upto is None else rel.keys[rel.labels <= upto]
            if len(keys):
                out[ftype] = keys
        return out

    def query(self, atoms: list, upto: int) -> set:
        """Distinct bindings of ``atoms`` (``[ftype, s, p, o]`` with ids
        or ``?x``) over the facts with label ``<= upto``, as a set of
        tuples of ``(var, id)`` in variable order."""
        b, bl = None, None
        for ftype, *slots in atoms:
            rel = self.rel.get(ftype)
            if rel is None:
                return set()
            rows = rel.select(slots)
            rows = rows[rel.labels[rows] <= upto]
            r, rl = self._match(rel.cols()[rows], rel.labels[rows], slots)
            if b is None:
                b, bl = r, rl
            else:
                b, bl = self._join(b, bl, r, rl)
        names = sorted(b)
        return set(zip(*[[(v, int(x)) for x in b[v]] for v in names]))
