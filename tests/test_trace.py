"""Spans and counters inside the program (``repro.core.trace``).

  * while tracing is off a span is one shared no-op context: a served
    stream builds no ``TraceAnnotation``, and its answers, stats and
    counters equal those of the same stream served with tracing on;
  * the host-sync count sees IRD's transfers, a warm pattern-index hit
    counts its documented two syncs (one per stage, as the route audit of
    ``benchmarks/roofline.py`` counts them), and
    ``EngineReport.host_syncs`` counts a nested bucket once;
  * ``ServedResult.dispatched_s`` lies between arrival and finish, and on a
    ``VirtualClock`` the queue wait is the known one;
  * ``ServeReport.bucket_sizes`` counts every bucket, singletons included.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

import repro.core  # noqa: F401  (x64 on, as in production)

from repro.core import trace
from repro.core.engine import AdHashEngine
from repro.core.substrate import trace_host_syncs
from repro.data.synthetic_rdf import Workload, lubm_like, lubm_queries
from repro.runtime.fault_injection import VirtualClock
from repro.serving import (Request, ServeConfig, ServedResult, ServeLoop,
                           open_loop_arrivals, replay_open_loop)

_DICT, _TRIPLES = lubm_like(n_universities=2, depts_per_univ=2,
                            profs_per_dept=2, students_per_prof=2)
_QS = lubm_queries(_DICT)


@pytest.fixture(autouse=True)
def _tracing_off():
    trace.enable(False)
    yield
    trace.enable(False)


def _loop(eng, service_s=0.02, **cfg):
    return ServeLoop(eng, ServeConfig(**cfg), clock=VirtualClock(),
                     service_model=lambda n: service_s)


def _serve(tracing: bool):
    eng = AdHashEngine(_TRIPLES, 3, adaptive=True, frequency_threshold=2,
                       capacity=256)
    loop = _loop(eng, slo_s=5.0)
    arrivals = open_loop_arrivals(Workload(_DICT, seed=3).sample(24), 40.0,
                                  seed=4)
    trace.enable(tracing)
    try:
        done, rejected = replay_open_loop(loop, arrivals)
    finally:
        trace.enable(False)
    served = {c.rid: c for c in done if isinstance(c, ServedResult)}
    return eng, loop, served, rejected


def test_span_off_is_one_shared_noop():
    assert trace.span("control", rid=1) is trace.span("plan")
    n = trace.host_syncs()
    with trace.sync("total"):
        pass
    assert trace.host_syncs() == n + 1


def test_tracing_off_builds_nothing_and_on_changes_nothing(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("TraceAnnotation built while tracing is off")

    with monkeypatch.context() as m:
        m.setattr(jax.profiler, "TraceAnnotation", refuse)
        off = _serve(tracing=False)
    on = _serve(tracing=True)
    (eng_a, loop_a, served_a, rej_a), (eng_b, loop_b, served_b, rej_b) = \
        off, on
    assert not rej_a and not rej_b
    assert sorted(served_a) == sorted(served_b) and served_a
    assert any(c.stats.mode == "parallel-replica"
               for c in served_a.values())
    for rid, a in served_a.items():
        b = served_b[rid]
        assert a.relation.to_set() == b.relation.to_set(), rid
        assert (a.stats.mode, a.stats.route, a.stats.comm_cells,
                a.stats.n_retries, a.stats.plan) == \
            (b.stats.mode, b.stats.route, b.stats.comm_cells,
             b.stats.n_retries, b.stats.plan), rid
        assert (a.finished_s, a.dispatched_s) == (b.finished_s,
                                                  b.dispatched_s)
    ra, rb = vars(eng_a.report), vars(eng_b.report)
    assert {k: v for k, v in ra.items() if k != "history"} == \
        {k: v for k, v in rb.items() if k != "history"}
    assert vars(loop_a.report) == vars(loop_b.report)
    assert eng_a.report.host_syncs > 0


def test_syncs_of_a_redistribution_are_counted():
    """IRD's overflow checks and its barrier's counters go through the
    chokepoints: ``observe`` runs no query, so every sync it counts is
    the redistribution's."""
    eng = AdHashEngine(_TRIPLES, 3, adaptive=True, frequency_threshold=2,
                       capacity=256)
    q = _QS["q7"].instantiate(np.random.default_rng(0))
    counts = []
    for _ in range(4):
        before = eng.report.n_redistributions
        with trace_host_syncs() as tr:
            eng.observe(q)
        counts.append((eng.report.n_redistributions - before,
                       tr.host_transfers))
    assert any(n > 0 for n, _ in counts)
    for n, syncs in counts:
        assert (syncs > 0) == (n > 0), counts


def test_warm_pattern_index_hit_counts_two_syncs():
    """A warm hit of the two-pattern q12 syncs once per stage: the first
    match and one local probe join; the engine's report counts the
    same."""
    eng = AdHashEngine(_TRIPLES, 3, adaptive=True, frequency_threshold=2,
                       capacity=256)
    q = _QS["q12"].instantiate(np.random.default_rng(1))
    for _ in range(4):
        eng.query(q)
    before = eng.report.host_syncs
    with trace_host_syncs() as tr:
        rel, st = eng.query(q)
    assert st.mode == "parallel-replica" and st.n_retries == 0
    assert tr.host_transfers == 2, tr.host_transfers
    assert eng.report.host_syncs - before == 2
    # fetching the answer's rows is one more, outside the engine
    with trace_host_syncs() as tr:
        rel.to_numpy()
    assert tr.host_transfers == 1


def test_engine_report_counts_a_nested_bucket_once():
    """``query_batch`` runs buckets inside IRD's overlap, one engine call
    inside another: the report counts each transfer once."""
    eng = AdHashEngine(_TRIPLES, 3, adaptive=True, frequency_threshold=2,
                       capacity=256)
    batch = Workload(_DICT, seed=5).sample(16)
    with trace_host_syncs() as tr:
        eng.query_batch(batch)
    assert eng.report.n_redistributions > 0
    assert eng.report.host_syncs == tr.host_transfers > 0


def test_dispatch_time_and_queue_wait_on_a_virtual_clock():
    """Three same-shape requests arrive at once; each bucket fills at one
    member and costs 0.1 s, so they wait 0, 0.1 and 0.2 s."""
    eng = AdHashEngine(_TRIPLES, 3, adaptive=False, capacity=256)
    loop = _loop(eng, service_s=0.1, batch_target=1, slo_s=10.0)
    q = _QS["q7"].instantiate(np.random.default_rng(2))
    for rid in range(3):
        assert loop.offer(Request(rid, q, arrival_s=0.0)) is None
    done = loop.pump() + loop.drain()
    served = sorted((c for c in done if isinstance(c, ServedResult)),
                    key=lambda c: c.rid)
    assert [c.dispatched_s for c in served] == pytest.approx([0.0, 0.1, 0.2])
    assert [c.finished_s for c in served] == pytest.approx([0.1, 0.2, 0.3])
    assert loop.report.bucket_sizes == {1: 3}


def test_dispatch_time_lies_between_arrival_and_finish():
    eng, loop, served, _ = _serve(tracing=False)
    arrival = {r.rid: r.arrival_s for r in open_loop_arrivals(
        Workload(_DICT, seed=3).sample(24), 40.0, seed=4)}
    for rid, c in served.items():
        assert arrival[rid] <= c.dispatched_s <= c.finished_s, rid
    # pattern-index hits run inline; the rest went through buckets, every
    # one of them counted, singletons included
    bucketed = sum(1 for c in served.values()
                   if c.stats.mode != "parallel-replica")
    sizes = loop.report.bucket_sizes
    assert sum(n * k for n, k in sizes.items()) == bucketed
    assert sum(sizes.values()) == (loop.report.flush_full
                                   + loop.report.flush_deadline
                                   + loop.report.flush_pressure
                                   + loop.report.flush_drain
                                   + loop.report.flush_overlap)


def test_relation_rows_on_the_host_cost_no_sync():
    eng = AdHashEngine(_TRIPLES, 3, adaptive=False, capacity=256)
    qs = [_QS["q7"].instantiate(np.random.default_rng(i)) for i in range(2)]
    (rel, _), _ = eng.query_batch(qs)
    assert isinstance(rel.cols, np.ndarray)
    with trace_host_syncs() as tr:
        rel.to_numpy()
    assert tr.host_transfers == 0
