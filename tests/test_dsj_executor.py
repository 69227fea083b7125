"""DSJ + Algorithm 1 executor vs the brute-force oracle.

Covers the paper's worked examples (§4.1: Tables 3-5, both orderings of the
Figure 2 query; the Q_prof 3-pattern query of §4.1.2) and randomized graphs.
"""
from __future__ import annotations

import numpy as np
import pytest

import repro.core  # noqa: F401  (enables x64)
from repro.core.executor import Executor
from repro.core.partition import partition_by_subject
from repro.core.query import Query, TriplePattern, Var
from repro.core.triples import ShardedTripleStore

from paper_example import (
    c,
    expected_fig2,
    load_example,
    prof_query,
    prof_query3,
    v,
)
from reference import match_query


def make_store(triples: np.ndarray, w: int) -> ShardedTripleStore:
    assign = partition_by_subject(triples, w)
    return ShardedTripleStore.build(triples, assign, w)


def run(store, w, query, ordering, join_vars, cap=64):
    ex = Executor(store, w)
    rel, stats = ex.execute(query, ordering, join_vars, capacity=cap)
    return rel, stats


@pytest.mark.parametrize("w", [1, 2, 4])
@pytest.mark.parametrize("ordering", [[0, 1], [1, 0]])
def test_fig2_query_both_orderings(w, ordering):
    """q1 |><| q2 and q2 |><| q1 give identical results (Tables 4 vs 5)."""
    d, triples = load_example()
    store = make_store(triples, w)
    q = prof_query(d)
    rel, stats = run(store, w, q, ordering, [Var("prof")])
    got = set(map(tuple, rel.project_to([Var("prof"), Var("stud")])))
    assert got == expected_fig2(d)
    # q1 first: join col of q2 is its object -> broadcast (case iii)
    # q2 first: join col of q1 is its subject -> hash distribute (case ii)
    if w > 1:
        kind = "bcast" if ordering == [0, 1] else "hash"
        assert any(kind in step for step in stats.plan), stats.plan


@pytest.mark.parametrize("w", [2, 4])
def test_qprof_pinned_subject_local_join(w):
    """§4.1.2: ordering q2,q1,q3 makes the q3 join communication-free."""
    d, triples = load_example()
    store = make_store(triples, w)
    q = prof_query3(d)
    # ordering q2, q1, q3 -> pinned subject = ?stud -> q3 joins locally
    rel, stats = run(store, w, q, [1, 0, 2], [Var("prof"), Var("stud")])
    ref = match_query(triples, q)
    got = set(map(tuple, rel.project_to(q.vars)))
    assert got == ref
    assert stats.n_local_joins == 1, stats.plan
    assert stats.n_dsj == 1, stats.plan

    # ordering q1, q2, q3 -> both joins need communication (Fig. 5a)
    rel2, stats2 = run(store, w, q, [0, 1, 2], [Var("prof"), Var("stud")])
    got2 = set(map(tuple, rel2.project_to(q.vars)))
    assert got2 == ref
    assert stats2.n_dsj == 2, stats2.plan
    if w > 1:
        assert stats2.comm_cells >= stats.comm_cells


@pytest.mark.parametrize("w", [1, 3, 4])
def test_subject_star_no_comm(w):
    """Subject stars run in parallel mode — zero communication (§4.1)."""
    d, triples = load_example()
    store = make_store(triples, w)
    q = Query(
        [
            TriplePattern(v("s"), c(d, "advisor"), v("p")),
            TriplePattern(v("s"), c(d, "uGradFrom"), v("u")),
        ]
    )
    rel, stats = run(store, w, q, [0, 1], [Var("s")])
    assert stats.comm_cells == 0
    assert stats.mode == "parallel"
    assert set(map(tuple, rel.project_to(q.vars))) == match_query(triples, q)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("w", [1, 4])
def test_random_graph_chain_query(seed, w):
    rng = np.random.default_rng(seed)
    n_v, n_p, n_t = 40, 4, 300
    triples = np.unique(
        np.stack(
            [
                rng.integers(0, n_v, n_t),
                n_v + rng.integers(0, n_p, n_t),
                rng.integers(0, n_v, n_t),
            ],
            axis=1,
        ).astype(np.int64),
        axis=0,
    )
    store = make_store(triples, w)
    from repro.core.query import Const

    q = Query(
        [
            TriplePattern(v("a"), Const(n_v + 0), v("b")),
            TriplePattern(v("b"), Const(n_v + 1), v("c")),
            TriplePattern(v("c"), Const(n_v + 2), v("d")),
        ]
    )
    ref = match_query(triples, q)
    for ordering, join_vars in [
        ([0, 1, 2], [Var("b"), Var("c")]),
        ([1, 0, 2], [Var("b"), Var("c")]),
        ([2, 1, 0], [Var("c"), Var("b")]),
    ]:
        rel, _ = run(store, w, q, ordering, join_vars, cap=512)
        got = set(map(tuple, rel.project_to(q.vars)))
        assert got == ref, (ordering, len(got), len(ref))


@pytest.mark.parametrize("w", [4])
def test_object_object_join(w):
    """Object-object joins force broadcast (case iii) but stay correct."""
    d, triples = load_example()
    store = make_store(triples, w)
    q = Query(
        [
            TriplePattern(v("x"), c(d, "uGradFrom"), v("u")),
            TriplePattern(v("y"), c(d, "gradFrom"), v("u")),
        ]
    )
    rel, stats = run(store, w, q, [0, 1], [Var("u")])
    assert set(map(tuple, rel.project_to(q.vars))) == match_query(triples, q)
    assert stats.n_dsj == 1


def test_single_pattern_and_constants():
    d, triples = load_example()
    store = make_store(triples, 2)
    q = Query([TriplePattern(v("s"), c(d, "advisor"), c(d, "Bill"))])
    rel, stats = run(store, 2, q, [0], [])
    assert stats.comm_cells == 0
    got = {r[0] for r in rel.to_numpy()}
    assert got == {d.lookup("Lisa"), d.lookup("John"), d.lookup("Fred")}


def test_wide_projection_is_cut_to_its_live_prefix(monkeypatch):
    """A projection far wider than its live values is exchanged at the
    class of its live prefix; answers, sequential and batched, are those
    of the brute-force matcher."""
    from repro.core import executor as ex
    from repro.core.backend import quantize_capacity
    from repro.core.engine import AdHashEngine
    from repro.data.synthetic_rdf import lubm_like, lubm_queries

    widths = []
    cut = ex._live_projection

    def recording(proj, pvalid, n_live, cap_proj):
        out = cut(proj, pvalid, n_live, cap_proj)
        widths.append((cap_proj, out[2], n_live, out[0].shape[-1]))
        return out

    monkeypatch.setattr(ex, "_live_projection", recording)
    d, triples = lubm_like(2, 2, 2, 3, 2, seed=3)
    eng = AdHashEngine(triples, 4, dictionary=d, adaptive=False,
                       capacity=1 << 14)
    rng = np.random.default_rng(3)
    queries = [t.instantiate(rng) for t in lubm_queries(d).values()]
    for q in queries:
        rel, _ = eng.query(q)
        assert rel.project_to(q.vars).shape[1] == len(q.vars)
        assert set(map(tuple, rel.project_to(q.vars).tolist())) == \
            match_query(triples, q), q.name
    for q, (rel, _) in zip(queries, eng.query_batch(queries + queries)):
        assert set(map(tuple, rel.project_to(q.vars).tolist())) == \
            match_query(triples, q), q.name
    assert widths and all(
        new == quantize_capacity(n_live, floor=ex._PROJ_FLOOR) < old == 1 << 14
        and shp_new == new
        for old, new, n_live, shp_new in widths)


def test_live_cut_compiles_once_per_class(monkeypatch):
    """The cut makes the exchange width depend on each instance's live
    count, so a warm stream of one template compiles the downstream stages
    once per capacity class its live counts reach, and never again: an
    instance whose class was seen before compiles nothing, and the classes
    are bounded by log2(capacity / floor) + 1."""
    from repro.core import backend as be
    from repro.core import executor as ex
    from repro.core.engine import AdHashEngine
    from repro.core.query import Const

    monkeypatch.setattr(ex, "_PROJ_FLOOR", 4)
    widths = []
    cut = ex._live_projection

    def recording(proj, pvalid, n_live, cap_proj):
        out = cut(proj, pvalid, n_live, cap_proj)
        widths.append(out[2])
        return out

    monkeypatch.setattr(ex, "_live_projection", recording)
    # hubs of out-degree 1 .. 200 over one predicate, so the projection of
    # ?y after the constant pattern spans several classes
    knows, likes, rows, obj = 1, 2, [], 10_000
    for i, degree in enumerate([1, 3, 9, 27, 81, 200]):
        for _ in range(degree):
            rows += [(100 + i, knows, obj), (obj, likes, 50_000 + obj % 7)]
            obj += 1
    triples = np.array(rows, dtype=np.int64)
    cap = 1 << 10
    eng = AdHashEngine(triples, 4, adaptive=False, capacity=cap)

    def hub(i):
        return Query([TriplePattern(Const(100 + i), Const(knows), Var("y")),
                      TriplePattern(Var("y"), Const(likes), Var("z"))],
                     name="hub")

    stream = [hub(i) for i in (0, 1, 0, 2, 3, 1, 4, 5, 3, 5, 0)]
    seen = set()
    for pass_ in range(2):
        for q in stream:
            widths.clear()
            before = be.probe_compile_cache_size()
            rel, st = eng.query(q)
            assert st.n_dsj == 1
            assert set(map(tuple, rel.project_to(q.vars).tolist())) == \
                match_query(triples, q)
            (width,) = widths
            if width in seen:
                assert be.probe_compile_cache_size() == before, (pass_, width)
            seen.add(width)
    assert 3 <= len(seen) <= (cap // 4).bit_length()


@pytest.mark.parametrize("batched", [False, True])
def test_narrow_floor_keeps_answers_and_accounting(monkeypatch, batched):
    """Q7- and Q12-shaped DSJ queries with the projection floor at 512
    answer and account exactly as a twin engine with the floor at 4,096:
    the padding past the live prefix carries no valid value.  Only the
    probe slots searched shrink, 8x for steps under 512 live values, and
    counting them costs no host sync."""
    from repro.core import executor as ex
    from repro.core.engine import AdHashEngine
    from repro.data.synthetic_rdf import lubm_like, lubm_queries

    d, triples = lubm_like(2, 2, 2, 3, 2, seed=3)
    tpl = lubm_queries(d)
    queries = [tpl["q7"].make(p) for p in tpl["q7"].constants[:6]] + \
        [tpl["q12"].make(u) for u in tpl["q12"].constants]
    w, floor = 4, ex._PROJ_FLOOR

    def serve(floor):
        monkeypatch.setattr(ex, "_PROJ_FLOOR", floor)
        eng = AdHashEngine(triples, w, dictionary=d, adaptive=False,
                           capacity=1 << 12)
        if batched:
            out = eng.query_batch(queries)
        else:
            out = [eng.query(q) for q in queries]
        return eng.report, [(rel.project_to(q.vars), st)
                            for q, (rel, st) in zip(queries, out)]

    wide_report, wide = serve(1 << 12)
    narrow_report, narrow = serve(floor)
    for q, (rows_w, st_w), (rows_n, st_n) in zip(queries, wide, narrow):
        assert np.array_equal(rows_n, rows_w), q.name
        assert set(map(tuple, rows_n.tolist())) == match_query(triples, q)
        assert st_n.n_dsj >= 1, q.name
        for key in ("comm_cells", "n_retries", "mode", "route", "n_dsj"):
            assert getattr(st_n, key) == getattr(st_w, key), (q.name, key)
        # every step here holds far fewer than 512 live values
        assert st_n.probe_slots == st_n.n_dsj * w * w * floor == \
            st_n.n_dsj * w * w * 512, q.name
        assert st_w.probe_slots == 8 * st_n.probe_slots, q.name
    assert narrow_report.probe_slots == sum(st.probe_slots
                                            for _, st in narrow)
    assert wide_report.probe_slots == 8 * narrow_report.probe_slots
    assert narrow_report.comm_cells == wide_report.comm_cells
    assert narrow_report.host_syncs == wide_report.host_syncs > 0
