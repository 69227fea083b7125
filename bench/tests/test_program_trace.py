"""``bench/program_trace.py``: the reduction of the program's spans, exact
on synthetic nested events, and a tiny cell's windows on the CPU, whose
recorded trace holds the program's spans with their requests."""
import pytest

from bench import program_trace as pt
from bench import run
from tiny import TINY


def test_self_times_subtract_direct_children():
    spans = [("adhash.control", 0.0, 100.0), ("adhash.plan", 10.0, 20.0),
             ("adhash.execute", 30.0, 90.0), ("adhash.sync", 40.0, 70.0),
             ("adhash.sync", 80.0, 85.0), ("adhash.offer", 120.0, 125.0)]
    got = sorted(pt.self_times(spans))
    assert got == sorted([("adhash.control", 30.0), ("adhash.plan", 10.0),
                          ("adhash.execute", 25.0), ("adhash.sync", 30.0),
                          ("adhash.sync", 5.0), ("adhash.offer", 5.0)])


def test_reduce_program_on_synthetic_events():
    t = ("/host:CPU", 0)
    other = ("/host:CPU", 1)
    spans = [("adhash.control", t, 0.0, 100.0, {"rid": 1}),
             ("adhash.execute", t, 30.0, 90.0, {"rid": 1}),
             ("adhash.sync", t, 40.0, 70.0, {"rid": 1, "what": "total"}),
             ("adhash.sync", t, 80.0, 85.0, {"rid": 1, "what": "rows"}),
             ("adhash.offer", other, 150.0, 160.0, {"rid": 2}),
             # before the window: left out
             ("adhash.offer", other, -50.0, -40.0, {"rid": 0})]
    device = {"/device:TPU:0": [("op", 40.0, 70.0), ("op", 120.0, 140.0)]}
    r = pt.reduce_program(device, (0.0, 200.0), spans)
    # control 100 - 60 (execute) = 40; execute 60 - 35 (syncs) = 25; the
    # syncs wait on the device; offer 10
    assert r["host_self_s"] == pytest.approx(75e-9)
    assert r["self_by_span"]["sync"] == pytest.approx(35e-9)
    # idle [0,40) [70,120) [140,200); program spans cover [0,100) and
    # [150,160): 40 + 30 + 10 = 80 of 200
    assert r["idle_in_program_share"] == pytest.approx(0.4)
    assert r["idle_by_span"]["control"] == pytest.approx(70e-9)
    assert r["idle_by_span"]["sync"] == pytest.approx(5e-9)
    assert r["syncs_by_what"] == {"total": 1, "rows": 1}


def test_device_time_by_scope_reads_the_scope_path():
    ops = [("d", 0.0, 10.0, {"tf_op": "jit_f/probe/sort"}),
           ("d", 5.0, 12.0, {"tf_op": "jit_f/probe_and_reply/expand/x"}),
           ("d", 20.0, 30.0, {"long_name": "%copy = s32[4] copy(...)"})]
    r = pt.device_by_scope(ops, (0.0, 25.0))
    assert r["by_scope"] == pytest.approx(
        {"probe": 10e-9, "expand": 7e-9, "unscoped": 5e-9})
    assert r["stat_keys"] == ["long_name", "tf_op"]


@pytest.fixture(scope="module")
def closed():
    spec = run.cell_spec("lubm100-adhash-na.hot-closed")
    spec["config"].update(n_universities=2, cardinalities=TINY,
                          ingest_chunk_rows=97)
    return spec, run.prepare(spec, 2**32 + 23)


def _inside(span, outers) -> bool:
    return any(s <= span[2] and span[3] <= e for _, s, e in outers)


def test_a_traced_window_holds_the_program_spans_with_their_requests(
        closed):
    spec, sess = closed
    w = pt.window(sess, 31, 1.0, traced=True)
    bench_spans, spans = w["spans"]
    answered = [r for r in w["requests"] if r.status == "answered"]
    assert answered
    outer = {n: [sp for sp in bench_spans if sp[0] == "bench." + n]
             for n in ("offer", "pump", "drain", "fetch")}
    names = {sp[0] for sp in spans}
    assert {"adhash.offer", "adhash.control", "adhash.plan",
            "adhash.bucket", "adhash.sync", "adhash.execute"} <= names

    def rids(sp):
        st = sp[4]
        return ({int(st["rid"])} if "rid" in st
                else {int(x) for x in str(st["rids"]).split()})

    for sp in spans:
        if sp[4].get("what") == "relation":
            # the benchmark's client fetching an answer's rows
            assert _inside(sp, outer["fetch"]), sp
            continue
        if sp[0] == "adhash.offer":
            assert _inside(sp, outer["offer"]), sp
        elif sp[0] in ("adhash.control", "adhash.plan", "adhash.bucket",
                       "adhash.sync"):
            assert _inside(sp, outer["pump"] + outer["drain"]), sp
        assert rids(sp), sp
    for r in answered:
        for name in ("adhash.offer", "adhash.control", "adhash.plan",
                     "adhash.bucket", "adhash.sync"):
            assert any(sp[0] == name and r.rid in rids(sp) for sp in spans), \
                (name, r.rid)
    bucket = [sp for sp in spans if sp[0] == "adhash.bucket"]
    assert all(int(sp[4]["size"]) == len(rids(sp)) and sp[4]["reason"]
               for sp in bucket)


def test_the_program_numbers_of_a_tiny_cell(closed):
    spec, sess = closed
    for traced in (False, True):
        w = pt.window(sess, 32, 1.0, traced=traced)
        answered = [r for r in w["requests"] if r.status == "answered"]
        out = run.evaluate(spec, sess, w, sess.graph.triples)
        assert run.is_correct(out["checks"])
        got = pt.per_layer(w)
        for r in answered:
            res = w["results"][r.rid]
            assert r.due <= res.dispatched_s <= res.finished_s
        waits = sorted(w["results"][r.rid].dispatched_s - r.due
                       for r in answered)
        assert got["queue_wait_ms"] == pytest.approx(
            1e3 * waits[-(-95 * len(waits) // 100) - 1])
        assert got["queue_wait_ms"] < out["e2e"]["p95_ms"]
        # no pattern index in AdHash-NA: every request went through a bucket
        sizes = w["bucket_sizes"]
        assert sum(n * k for n, k in sizes.items()) == len(answered)
        assert got["requests_per_dispatch"] == pytest.approx(
            len(answered) / sum(sizes.values()))
        assert got["host_syncs_per_query"] >= 1
        assert got["host_syncs_per_query"] == pytest.approx(
            w["host_syncs"] / len(answered))
        if traced:
            assert got["host_self_ms_per_query"] > 0
            idle = 100.0 * w["trace"]["idle_share"]
            assert 0 <= got["idle_in_program"] <= idle
            assert sum(w["program"]["syncs_by_what"].values()) >= \
                w["host_syncs"]
        else:
            assert "host_self_ms_per_query" not in got
