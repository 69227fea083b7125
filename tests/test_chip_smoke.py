"""``chip_smoke.py`` and what it relies on, at tiny size on the CPU.

  * the numpy BGP evaluator (``reference.evaluate_bgp``, the smoke's oracle
    at store scale) against the brute-force matcher on small graphs;
  * the smoke's phase function end to end (load, every template, IRD,
    batch, serving) with the ``pallas`` backend — the same code the chip
    runs, minus the size;
  * its sharded path (``--chips 4``) over four host devices, in a child
    process that forces them;
  * the smoke refusing to run without a TPU, printing no result;
  * the ``pallas`` backend's per-primitive record of kernel vs jnp;
  * the compile-cache helper's placement rules.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import repro.core  # noqa: F401  (x64 on, as in production)
import jax
import jax.numpy as jnp

from reference import bindings_array, evaluate_bgp, match_query
from repro.core.query import Const, Query, TriplePattern, Var
from repro.data.synthetic_rdf import lubm_like, lubm_queries

ROOT = Path(__file__).resolve().parent.parent
TINY = dict(n_universities=2, depts_per_univ=2, profs_per_dept=2,
            students_per_prof=3, courses_per_prof=2)


def _same(triples, q):
    got = evaluate_bgp(triples, q)
    want = bindings_array(match_query(triples, q), len(q.vars))
    np.testing.assert_array_equal(got, want)
    return len(want)


@pytest.mark.parametrize("seed", [0, 1])
def test_numpy_reference_matches_brute_force_on_templates(seed):
    d, triples = lubm_like(**TINY, seed=seed)
    rng = np.random.default_rng(seed)
    rows = 0
    for tpl in lubm_queries(d).values():
        for _ in range(3):
            rows += _same(triples, tpl.instantiate(rng))
    assert rows > 0


def test_numpy_reference_matches_brute_force_on_edge_shapes():
    """Repeated variables, variable predicates, a cross product, a
    multi-variable join key and an empty answer."""
    rng = np.random.default_rng(7)
    triples = np.unique(rng.integers(0, 6, (120, 3)), axis=0)
    x, y, z, p = Var("x"), Var("y"), Var("z"), Var("p")
    shapes = [
        [TriplePattern(x, Const(1), x)],
        [TriplePattern(x, p, y), TriplePattern(y, p, z)],
        [TriplePattern(x, Const(2), Const(3)),
         TriplePattern(y, Const(4), z)],
        [TriplePattern(x, Const(0), y), TriplePattern(x, Const(5), y)],
        [TriplePattern(x, Const(99), y)],
    ]
    for pats in shapes:
        _same(triples, Query(pats))


@pytest.fixture(scope="module")
def chip_smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke as mod
    finally:
        sys.path.remove(str(ROOT))
    return mod


def test_smoke_phases_at_tiny_size(chip_smoke):
    logs: list[str] = []
    rec = chip_smoke.run_smoke(TINY, seed=0, backend="pallas",
                               log=logs.append)
    assert rec["backend"] == "pallas" and rec["devices"] == 1
    assert rec["triples"] > 0 and rec["store_bytes"] > 0
    assert set(rec["seconds"]) == {"data", "load", "queries", "adaptivity",
                                   "batch", "serving"}
    routes = rec["routes"]
    assert set(routes) == {"q1", "q2", "q7", "q9", "q4chain", "q12"}
    assert routes["q1"][1].endswith("-local-main")
    assert routes["q7"][2] > 0  # distributed semi-join
    assert rec["programs"] > 0
    # off-TPU the probe kernel runs interpreted; the relalg primitives run
    # their jnp mirrors on every platform — all of it recorded by name
    assert rec["impls"]["probe"].get("kernel", 0) > 0
    for op in ("expand", "bucket_by_dest", "unique_compact"):
        assert set(rec["impls"][op]) == {"jnp"}, op
    assert any("pattern-index hit" in line for line in logs)


_SHARDED = """
import json, sys
sys.path.insert(0, {root!r})
import chip_smoke
rec = chip_smoke.run_sharded({sizes!r}, 4, backend="pallas", log=print)
print(json.dumps({{"devices": rec["devices"], "backend": rec["backend"],
                  "phases": sorted(rec["seconds"])}}))
"""


def test_sharded_phases_over_four_host_devices():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED.format(root=str(ROOT), sizes=TINY)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "store spans 4 devices" in out.stdout
    rec = json.loads(out.stdout.splitlines()[-1])
    assert rec == {"devices": 4, "backend": "pallas",
                   "phases": ["batch", "data", "load", "queries"]}


def test_smoke_refuses_to_run_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_pallas_backend_records_kernel_and_jnp_choices():
    """Inside the probe kernel's envelope the pallas backend runs the
    kernel, past it the binary search; each choice is counted by name."""
    from repro.core import backend as B
    from repro.kernels.semijoin.semijoin import probe_fits

    def counts():
        return dict(B.resolved_impls().get("probe", {}))

    small = (jax.ShapeDtypeStruct((4096,), jnp.int64),
             jax.ShapeDtypeStruct((1024,), jnp.int64))
    big = (jax.ShapeDtypeStruct((1 << 22,), jnp.int64),
           jax.ShapeDtypeStruct((1 << 16,), jnp.int64))
    assert probe_fits(4096, 1024) and not probe_fits(1 << 22, 1 << 16)
    before = counts()
    jax.eval_shape(lambda k, p: B.range_search(k, p, "pallas"), *small)
    mid = counts()
    assert mid.get("kernel", 0) == before.get("kernel", 0) + 1
    jax.eval_shape(lambda k, p: B.range_search(k, p, "pallas"), *big)
    after = counts()
    assert after.get("jnp", 0) == mid.get("jnp", 0) + 1
    # the searchsorted backend is not the pallas backend's business
    jax.eval_shape(lambda k, p: B.range_search(k, p, "searchsorted"),
                   *small)
    assert counts() == after


def test_compile_cache_placement(monkeypatch):
    from repro.compile_cache import CHECKOUT_CACHE, enable_compile_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == str(CHECKOUT_CACHE)
        assert CHECKOUT_CACHE == ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        # set from outside: JAX's own reading of the variable stands
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
