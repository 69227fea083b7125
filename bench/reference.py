"""The plain reference: exact distinct bindings of a basic graph pattern.

Independent of the engine: it reads the raw triple array, indexes it once
as two sorted key arrays (predicate-subject-object and predicate-object-
subject, each triple one int64 key), and answers a pattern by index
nested-loop joins.  Patterns need a constant predicate, as every template
has.  An answer is a lexicographically sorted (n, len(vars)) int64 array of
distinct rows, columns in the order asked for.
"""
from __future__ import annotations

import numpy as np


class Index:
    """Both sort orders of the triples, each as ``(p * M + x) * M + y``."""

    def __init__(self, triples: np.ndarray):
        t = np.asarray(triples, dtype=np.int64)
        self.m = int(t.max()) + 1 if len(t) else 1
        if (int(t[:, 1].max()) + 1) * self.m * self.m >= 2**63:
            raise ValueError("ids too large for one int64 key per triple")
        m = self.m
        self.pso = np.sort((t[:, 1] * m + t[:, 0]) * m + t[:, 2])
        self.pos = np.sort((t[:, 1] * m + t[:, 2]) * m + t[:, 0])

    def _range(self, p: int, first: np.ndarray, by_object: bool):
        keys = self.pos if by_object else self.pso
        base = (p * self.m + first.astype(np.int64)) * self.m
        return (keys, np.searchsorted(keys, base, side="left"),
                np.searchsorted(keys, base + self.m, side="left"))

    def count(self, p: int, first: int, by_object: bool) -> int:
        _, lo, hi = self._range(p, np.array([first]), by_object)
        return int(hi[0] - lo[0])

    def lookup(self, p: int, first: np.ndarray, by_object: bool):
        """For each value of the first key column, the matching triples:
        (row of ``first`` each belongs to, the other column's value)."""
        keys, lo, hi = self._range(p, first, by_object)
        counts = hi - lo
        owner = np.repeat(np.arange(len(first)), counts)
        start = np.repeat(lo - np.concatenate([[0], np.cumsum(counts)[:-1]]),
                          counts)
        hit = keys[start + np.arange(len(owner))]
        return owner, hit % self.m

    def predicate(self, p: int):
        """Every (s, o) of predicate ``p``."""
        k = self.pso[slice(*np.searchsorted(
            self.pso, [p * self.m * self.m, (p + 1) * self.m * self.m]))]
        return (k // self.m) % self.m, k % self.m


def _value(term, cols, n):
    """A term's value per binding row, or None where it is a free var."""
    kind, v = term
    if kind == "const":
        return np.full(n, v, np.int64)
    return cols.get(v)


def _estimate(index: Index, pat) -> int:
    s, p, o = pat
    if s[0] == "const":
        return index.count(p[1], s[1], by_object=False)
    if o[0] == "const":
        return index.count(p[1], o[1], by_object=True)
    return len(index.pso)  # no constant besides the predicate: last


def evaluate(index: Index, patterns, var_order: list[str]) -> np.ndarray:
    """Distinct bindings of ``patterns`` (each ``((kind, v), (kind, v),
    (kind, v))`` with kind ``"var"`` or ``"const"``), sorted."""
    for pat in patterns:
        if pat[1][0] != "const":
            raise ValueError("the reference needs a constant predicate")
    todo = sorted(patterns, key=lambda pat: _estimate(index, pat))
    cols: dict[str, np.ndarray] = {}
    n = 1
    while todo:
        # next: a pattern with a bound term, the most bound first
        def bound(pat):
            return sum(t[0] == "const" or t[1] in cols for t in (pat[0], pat[2]))
        pat = max(todo, key=bound)
        todo.remove(pat)
        s, p, o = pat
        sv, ov = _value(s, cols, n), _value(o, cols, n)
        if sv is not None:
            owner, other = index.lookup(p[1], sv, by_object=False)
            new = {} if ov is not None else {o[1]: other}
            keep = other == ov[owner] if ov is not None else None
        elif ov is not None:
            owner, other = index.lookup(p[1], ov, by_object=True)
            new, keep = {s[1]: other}, None
        else:
            ps, po = index.predicate(p[1])
            owner = np.repeat(np.arange(n), len(ps))
            new = {s[1]: np.tile(ps, n)}
            if o[1] == s[1]:
                keep = np.tile(po == ps, n)
            else:
                new[o[1]] = np.tile(po, n)
                keep = None
        cols = {v: c[owner] for v, c in cols.items()}
        cols.update(new)
        n = len(owner)
        if keep is not None:
            cols = {v: c[keep] for v, c in cols.items()}
            n = int(keep.sum())
        if n == 0:
            break
    if n == 0:
        return np.zeros((0, len(var_order)), np.int64)
    return sorted_rows(np.stack([cols[v] for v in var_order], axis=1),
                       distinct=True)


def sorted_rows(a: np.ndarray, distinct: bool = False) -> np.ndarray:
    """Rows of a 2-D int array in lexicographic order, optionally distinct."""
    a = np.asarray(a, dtype=np.int64)
    if len(a) == 0 or a.shape[1] == 0:
        return a
    s = a[np.lexsort(a.T[::-1])]
    if not distinct:
        return s
    keep = np.ones(len(s), bool)
    keep[1:] = np.any(s[1:] != s[:-1], axis=1)
    return s[keep]
