"""The trace reduction: busy-interval union, idle share and gap labels."""
import time

import pytest

from bench import traces


def test_union_merges_overlaps_and_drops_empty():
    got = traces.union([(5, 7), (0, 2), (1, 3), (6, 9), (4, 4)])
    assert got == [(0, 3), (5, 9)]


def test_reduce_on_synthetic_events():
    spans = [("bench.window", 0.0, 100.0), ("bench.wait", 10.0, 40.0),
             ("bench.pump", 40.0, 100.0)]
    device = {"/device:TPU:0": [("probe", 5.0, 10.0), ("expand", 8.0, 12.0),
                                ("probe", 40.0, 60.0), ("sort", -5.0, 2.0),
                                ("late", 99.0, 120.0)]}
    r = traces.reduce(device, spans)
    # busy: [0,2] + [5,12] + [40,60] + [99,100] = 2 + 7 + 20 + 1
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["idle_share"] == pytest.approx(0.7)
    assert dict((n, s) for n, s in r["device_ops"])["probe"] == \
        pytest.approx(25e-9)
    # gaps: [2,5] untraced-ish (window only), [12,40] mostly wait,
    # [60,99] pump
    assert r["idle_gaps"][0] == ["pump", pytest.approx(39e-9)]
    assert r["idle_gaps"][1] == ["wait", pytest.approx(28e-9)]
    assert r["idle_by_span"]["untraced"] == pytest.approx(3e-9)


def test_reduce_names_programs_where_the_trace_has_them():
    spans = [("bench.window", 0.0, 100.0)]
    device = {"d": [("%while.3 = u32[8] while(...)", 0.0, 30.0),
                    ("%fusion.1 = u32[8] fusion(...)", 5.0, 10.0),
                    ("%copy.2 = s32[3] copy(...)", 50.0, 60.0)]}
    programs = {"d": [("jit_probe(123)", 0.0, 30.0),
                      ("jit_copy(77)", 50.0, 60.0),
                      ("jit_probe(123)", 90.0, 110.0)]}
    r = traces.reduce(device, spans, programs)
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["device_ops"] == [["jit_probe", pytest.approx(40e-9)],
                               ["jit_copy", pytest.approx(10e-9)]]
    ops = traces.reduce(device, spans)["device_ops"]
    assert [n for n, _ in ops] == ["%while.3", "%copy.2", "%fusion.1"]


def test_reduce_averages_over_devices():
    spans = [("bench.window", 0.0, 10.0)]
    device = {"a": [("x", 0.0, 10.0)], "b": [("x", 0.0, 5.0)]}
    r = traces.reduce(device, spans)
    assert r["busy_s"] == pytest.approx(7.5e-9)
    assert r["idle_share"] == pytest.approx(0.25)


def test_reduce_refuses_a_trace_without_window():
    with pytest.raises(ValueError):
        traces.reduce({"a": [("x", 0.0, 1.0)]}, [])


def test_reduce_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x) * 2)
    x = jnp.arange(1 << 16)[::-1]
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.pump"):
            for _ in range(3):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.05)
    jax.profiler.stop_trace()
    r = traces.reduce_file(traces.find_xplane(tmp_path))
    assert 0.05 < r["window_s"] < 5
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_share"] < 1
    assert any(name.startswith("sort") for name, _ in r["device_ops"])
    assert r["idle_gaps"][0][0] == "wait"
    assert r["idle_gaps"][0][1] >= 0.04
