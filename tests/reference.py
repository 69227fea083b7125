"""Pure-python brute-force BGP matcher — the oracle for all engine tests."""
from __future__ import annotations

import itertools

import numpy as np

from repro.core.query import Const, Query, TriplePattern, Var


def match_query(triples: np.ndarray, query: Query) -> set[tuple[int, ...]]:
    """All bindings of query.vars (in query.vars order), brute force."""
    triples = np.asarray(triples)
    bindings: list[dict[Var, int]] = [dict()]
    for pat in query.patterns:
        new: list[dict[Var, int]] = []
        for b in bindings:
            for row in triples:
                nb = _match_one(pat, row, b)
                if nb is not None:
                    new.append(nb)
        bindings = new
        if not bindings:
            break
    out = set()
    for b in bindings:
        out.add(tuple(int(b[v]) for v in query.vars))
    return out


def _match_one(pat: TriplePattern, row: np.ndarray, b: dict[Var, int]
               ) -> dict[Var, int] | None:
    nb = dict(b)
    for term, val in zip((pat.s, pat.p, pat.o), row):
        val = int(val)
        if isinstance(term, Const):
            if term.id != val:
                return None
        else:
            if term in nb:
                if nb[term] != val:
                    return None
            else:
                nb[term] = val
    return nb


# --------------------------------------------------------------------------
# Vectorised numpy evaluator: the same semantics as ``match_query`` (distinct
# bindings of ``query.vars``) at store scale.  Independent of the engine: no
# partitioning, capacities or device code — per-pattern masks over the raw
# triple array, then hash-free joins on the shared variables.
def evaluate_bgp(triples: np.ndarray, query: Query) -> np.ndarray:
    """Distinct bindings as a lexicographically sorted (n, len(query.vars))
    int64 array, columns in ``query.vars`` order."""
    triples = np.asarray(triples, dtype=np.int64)
    remaining = list(query.patterns)
    vars_: list[Var] = []
    rows = np.zeros((1, 0), np.int64)  # one empty binding
    while remaining:
        # next pattern: one sharing a bound variable (a join, not a product)
        pick = next((p for p in remaining if set(p.vars) & set(vars_)),
                    remaining[0])
        remaining.remove(pick)
        pvars, pcols = _pattern_table(triples, pick)
        rows, vars_ = _join(rows, vars_, pcols, pvars)
        if not len(rows):
            break
    out = np.zeros((len(rows), len(query.vars)), np.int64)
    if len(rows):
        out = rows[:, [vars_.index(v) for v in query.vars]]
    return unique_rows(out)


def unique_rows(a: np.ndarray) -> np.ndarray:
    """Distinct rows of a 2-D int array, lexicographically sorted (what
    ``np.unique(a, axis=0)`` returns, without its slow record view)."""
    if len(a) == 0 or a.shape[1] == 0:
        return a[:min(len(a), 1)]
    s = a[np.lexsort(a.T[::-1])]
    keep = np.ones(len(s), bool)
    keep[1:] = np.any(s[1:] != s[:-1], axis=1)
    return s[keep]


def bindings_array(bindings: set[tuple[int, ...]], width: int) -> np.ndarray:
    """``match_query``'s set as ``evaluate_bgp``'s sorted array."""
    if not bindings:
        return np.zeros((0, width), np.int64)
    return unique_rows(np.array(sorted(bindings), np.int64).reshape(-1, width))


def _pattern_table(triples: np.ndarray, pat: TriplePattern
                   ) -> tuple[list[Var], np.ndarray]:
    """Rows matching one pattern, one column per distinct variable."""
    mask = np.ones(len(triples), bool)
    first_col: dict[Var, int] = {}
    for c, term in enumerate((pat.s, pat.p, pat.o)):
        if isinstance(term, Const):
            mask &= triples[:, c] == term.id
        elif term in first_col:  # repeated variable: equal columns
            mask &= triples[:, c] == triples[:, first_col[term]]
        else:
            first_col[term] = c
    sel = triples[mask]
    pvars = list(first_col)
    return pvars, sel[:, [first_col[v] for v in pvars]]


def _join(left: np.ndarray, lvars: list[Var], right: np.ndarray,
          rvars: list[Var]) -> tuple[np.ndarray, list[Var]]:
    """Natural join on the shared variables by sorted-key range expansion."""
    shared = [v for v in rvars if v in lvars]
    new = [v for v in rvars if v not in lvars]
    if len(shared) == 1:  # the ids themselves are the join keys
        lid = left[:, lvars.index(shared[0])]
        rid = right[:, rvars.index(shared[0])]
    elif shared:
        keys = np.concatenate([left[:, [lvars.index(v) for v in shared]],
                               right[:, [rvars.index(v) for v in shared]]])
        # dense ids for the composite key, shared by both sides
        order = np.lexsort(keys.T[::-1])
        s = keys[order]
        new_key = np.ones(len(s), bool)
        new_key[1:] = np.any(s[1:] != s[:-1], axis=1)
        inv = np.empty(len(s), np.int64)
        inv[order] = np.cumsum(new_key) - 1
        lid, rid = inv[:len(left)], inv[len(left):]
    else:  # cross product
        lid = np.zeros(len(left), np.int64)
        rid = np.zeros(len(right), np.int64)
    order = np.argsort(rid, kind="stable")
    rsorted = rid[order]
    lo = np.searchsorted(rsorted, lid, side="left")
    hi = np.searchsorted(rsorted, lid, side="right")
    counts = hi - lo
    li = np.repeat(np.arange(len(left)), counts)
    starts = np.repeat(lo - np.concatenate([[0], np.cumsum(counts)[:-1]]),
                       counts)
    ri = order[starts + np.arange(len(li))]
    cols = [left[li]] + [right[ri][:, [rvars.index(v) for v in new]]]
    return np.concatenate(cols, axis=1), lvars + new
