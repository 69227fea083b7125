"""A whole run of each cell at a tiny size on the CPU, bypassing only the
look for a chip: the request loops through ``ServeLoop`` on the wall clock,
counted exactly, and the check, which passes a sound run and fails the
control and every fault planted under the timed path."""
import time

import numpy as np
import pytest

from bench import run, serve
from tiny import TINY

CELLS = {"open": "lubm100-adhash.hot-open",
         "closed": "lubm100-adhash-na.hot-closed"}


def _spec(loop):
    spec = run.cell_spec(CELLS[loop])
    spec["config"].update(n_universities=2, cardinalities=TINY,
                          ingest_chunk_rows=97)
    return spec


@pytest.fixture(scope="module")
def sessions():
    return {}


def _session(sessions, loop):
    if loop not in sessions:
        spec = _spec(loop)
        sessions[loop] = (spec, run.prepare(spec, 2**32 + 17))
    return sessions[loop]


def _verdict(spec, sess, w, control=None):
    out = run.evaluate(spec, sess, w, sess.graph.triples, control)
    return out, run.is_correct(out["checks"])


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_sound_run_is_correct(sessions, loop):
    spec, sess = _session(sessions, loop)
    w = run.measure(sess, 5, 2.0, trace=False)
    out, ok = _verdict(spec, sess, w)
    assert ok, out["checks"]
    assert out["checks"]["checked"]["value"] == out["attempted"] > 0
    assert w["compiles_in_window"] == 0
    if loop == "open":
        assert out["attempted"] == round(spec["traffic"]["rate_qps"] * 2.0)
        assert run.reader("pi_hit_share")(out["record"]) > 0
    else:
        assert out["e2e"]["qps"] > 0


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_control_is_not_correct(sessions, loop):
    spec, sess = _session(sessions, loop)
    w = run.measure(sess, 6, 1.0, trace=False)
    out, ok = _verdict(spec, sess, w, control="truncate")
    assert not ok
    # the control cuts every answer but an empty one
    nonempty = sum(len(r.rows) > 0 for r in w["requests"])
    assert out["checks"]["wrong_answers"]["value"] == nonempty > 0


@pytest.mark.parametrize("loop,fault", [("open", "alter"),
                                        ("closed", "alter"),
                                        ("closed", "half_batch")])
def test_planted_fault_is_not_correct(sessions, loop, fault):
    spec, sess = _session(sessions, loop)
    w = run.measure(sess, 7, 1.5, trace=False, fault=fault)
    out, ok = _verdict(spec, sess, w)
    assert not ok
    assert out["checks"]["wrong_answers"]["value"] > 0
    # mended afterwards: the next window is sound again
    w = run.measure(sess, 8, 1.0, trace=False)
    assert _verdict(spec, sess, w)[1]


def test_shed_in_the_closed_loop_is_not_correct(sessions):
    """The closed loop's traffic promises that nothing is shed: a loop
    whose limit sheds its requests answers fewer and is not correct."""
    from repro.serving import ServeConfig

    spec, sess = _session(sessions, "closed")
    sess.cfg = ServeConfig(slo_s=1e-4)
    try:
        w = run.measure(sess, 9, 0.5, trace=False)
    finally:
        sess.cfg = ServeConfig(**spec["traffic"]["serve"])
    shed = sum(r.status == "shed" for r in w["requests"])
    out, ok = _verdict(spec, sess, w)
    assert shed > 0 and not ok
    assert out["checks"]["unanswered"]["value"] >= shed


def test_open_loop_counts_answered_shed_and_late(sessions):
    """Two requests due in the past are shed; of five spaced apart, two are
    made to take longer than the limit and come back late, three in
    time."""
    from repro.serving import ServeConfig

    spec, sess = _session(sessions, "open")
    slo = 0.3
    sess.cfg = ServeConfig(slo_s=slo, predictive_shed=False)
    step = sess.engine.stream_control_step
    reqs = [sess.requests.make("q12") for _ in range(7)]
    slow = {reqs[3].rid, reqs[5].rid}

    def slow_step(q, batcher, tag, *a, **kw):
        if tag in slow:
            time.sleep(slo + 0.1)
        return step(q, batcher, tag, *a, **kw)

    sess.engine.stream_control_step = slow_step
    try:
        loop = sess.new_loop()
        t0 = serve.now()
        for i, r in enumerate(reqs):
            r.due = t0 - 10.0 if i < 2 else t0 + 0.8 * (i - 2)
        serve.open_loop(loop, reqs, serve.Spans())
    finally:
        sess.engine.stream_control_step = step
        sess.cfg = ServeConfig(**spec["traffic"]["serve"])
    assert [r.status for r in reqs] == ["shed"] * 2 + ["answered"] * 5
    assert [r.latency > slo for r in reqs[2:]] == [False, True, False,
                                                   True, False]
    assert loop.report.shed == 2 and loop.report.late == 2
    assert loop.report.answered == 5


def test_closed_loop_keeps_every_client_busy_until_close(sessions):
    spec, sess = _session(sessions, "closed")
    loop = sess.new_loop()
    made = iter(["q1", "q7", "q12"] * 1000)
    close = serve.now() + 1.0
    reqs = serve.closed_loop(loop, lambda: sess.requests.make(next(made)),
                             3, close, serve.Spans())
    assert len(reqs) >= 3
    assert all(r.status == "answered" for r in reqs)
    assert loop.report.answered == len(reqs) == loop.report.offered
    # the last request of each client was sent before the close
    assert max(r.offered for r in reqs) < close
    # every answer's rows are on the host; on UBA data an answer may be
    # empty (a graduate course nobody takes)
    assert all(r.rows.shape[1] == len(r.vars) for r in reqs)
    assert any(len(r.rows) > 0 for r in reqs)
    assert np.all(np.isfinite([r.latency for r in reqs]))
