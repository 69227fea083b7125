"""Paper Tables 11-14 + Fig 11 + Fig 18: per-query runtimes and
communication for AdHash vs AdHash-NA vs the locality-blind baseline.

Three engine configurations (the §6.3.1 ablation):
  blind     locality_aware=False, pinned_opt=False  (SHARD-like broadcast)
  na        AdHash-NA: locality-aware, no adaptivity
  adaptive  full AdHash (after warming the heat map)

Also runs the worker-scaling sweep (Fig 18 strong scalability) and — ISSUE 2
— the batched multi-query throughput comparison (``run_batched``): warmed
sequential loop vs ``query_batch`` shape-bucketed dispatch, with dispatch
and recompile counts.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

import repro.core  # noqa: F401
from repro.core import backend as be
from repro.core.engine import AdHashEngine
from repro.data.synthetic_rdf import Workload, lubm_like


def _run_queries(eng, queries):
    t0 = time.perf_counter()
    comm = 0
    for q in queries:
        _, st = eng.query(q)
        comm += st.comm_cells
    return (time.perf_counter() - t0) * 1e6 / max(len(queries), 1), comm


def run(n_workers: int = 8) -> list[tuple[str, float, str]]:
    d, triples = lubm_like(n_universities=4, depts_per_univ=3,
                           profs_per_dept=4, students_per_prof=6)
    wl = Workload(d, seed=1)
    rows = []
    per_template = {
        name: [wl.templates[name].instantiate(wl.rng) for _ in range(6)]
        for name in wl.templates
    }

    blind = AdHashEngine(triples, n_workers, adaptive=False,
                         locality_aware=False, pinned_opt=False)
    na = AdHashEngine(triples, n_workers, adaptive=False)
    ad = AdHashEngine(triples, n_workers, adaptive=True,
                      frequency_threshold=3)

    for name, queries in per_template.items():
        us_blind, comm_blind = _run_queries(blind, queries)
        us_na, comm_na = _run_queries(na, queries)
        # warm AdHash so the pattern is redistributed, then measure
        _run_queries(ad, queries)
        us_ad, comm_ad = _run_queries(ad, queries)
        rows.append((f"queries/{name}/blind_us", us_blind,
                     f"comm_cells={comm_blind}"))
        rows.append((f"queries/{name}/adhash_na_us", us_na,
                     f"comm_cells={comm_na}"))
        rows.append((f"queries/{name}/adhash_us", us_ad,
                     f"comm_cells={comm_ad}"))
        # locality awareness must not increase communication (Fig 11b)
        assert comm_na <= comm_blind, (name, comm_na, comm_blind)
        # adapted execution is communication-free (paper's headline)
        assert comm_ad == 0, (name, comm_ad)

    # ---------------- Fig 18: strong scaling of parallel-mode queries
    for w in (2, 4, 8, 16):
        eng = AdHashEngine(triples, w, adaptive=True, frequency_threshold=2)
        qs = per_template["q1"]
        _run_queries(eng, qs)  # adapt
        us, comm = _run_queries(eng, qs)
        rows.append((f"scaling/q1/w{w}_us", us, f"comm_cells={comm}"))
    return rows


def _bench_one_mix(
    tag: str,
    templates: list[str] | None,
    n_workers: int,
    n_per_template: int,
    triples,
    d,
) -> list[tuple[str, float, str]]:
    wl = Workload(d, seed=2)
    names = sorted(templates or wl.templates)

    def workload():
        return [
            wl.templates[t].instantiate(wl.rng)
            for t in names
            for _ in range(n_per_template)
        ]

    # capacity 64 keeps the stage shapes in the dispatch-bound regime the
    # throughput claim is about (selective queries, small intermediates)
    seq = AdHashEngine(triples, n_workers, adaptive=False, capacity=64)
    bat = AdHashEngine(triples, n_workers, adaptive=False, capacity=64)
    # warm both paths on the same template mix (twice: past retry doublings)
    for _ in range(2):
        for q in workload():
            seq.query(q)
        bat.query_batch(workload())
    n = len(names) * n_per_template
    seq_trials, bat_trials = [], []
    recompiles = 0  # batched-path only: seq and bat share one jit cache
    dispatches0 = bat.report.n_batch_dispatches
    trials, reps = 7, 4  # reps lengthen each timed window past scheduler
    #                      jitter (a 48-query batched pass is ~50 ms alone)
    for _ in range(trials):
        # identical query list for both paths: apples-to-apples per trial
        qs = workload()
        t0 = time.perf_counter()
        for _ in range(reps):
            for q in qs:
                seq.query(q)
        seq_trials.append(time.perf_counter() - t0)
        cache0 = be.probe_compile_cache_size()
        t0 = time.perf_counter()
        for _ in range(reps):
            bat.query_batch(qs)
        bat_trials.append(time.perf_counter() - t0)
        recompiles += be.probe_compile_cache_size() - cache0
    # median, not best-of: the CI perf gate diffs these against a
    # checked-in baseline, so the statistic must be stable across runs on
    # a shared host, not the luckiest scheduling window
    seq_s = float(np.median(seq_trials)) / reps
    bat_s = float(np.median(bat_trials)) / reps
    seq_qps = n / seq_s
    bat_qps = n / bat_s
    n_disp = (bat.report.n_batch_dispatches - dispatches0) // (trials * reps)
    return [
        (f"batch/{tag}/w{n_workers}/sequential_qps", seq_qps,
         f"us_per_query={seq_s * 1e6 / n:.1f}"),
        (f"batch/{tag}/w{n_workers}/batched_qps", bat_qps,
         f"us_per_query={bat_s * 1e6 / n:.1f}"),
        (f"batch/{tag}/w{n_workers}/speedup_x", bat_qps / seq_qps,
         f"n_queries={n}"),
        (f"batch/{tag}/w{n_workers}/dispatches", float(n_disp),
         f"sequential_dispatches={n}"),
        (f"batch/{tag}/w{n_workers}/post_warm_recompiles", float(recompiles),
         "must_be_zero"),
    ]


def run_batched(n_workers: int = 8, n_per_template: int = 16
                ) -> list[tuple[str, float, str]]:
    """Batched vs sequential workload throughput (ISSUE 2 acceptance).

    Both engines are warmed first, then a fresh same-template workload
    (different constants) is timed through the sequential loop and through
    ``query_batch``.  Reports queries/s for both paths, the speedup,
    dispatch counts and post-warmup recompiles (must be zero — the
    capacity/batch-size classes at work).

    The headline mix is the constant-instantiated templates (q1/q7/q12):
    those are the queries that realistically hit the distributed path at
    high frequency — constant-free templates repeat *identical* queries,
    which adaptive AdHash redistributes into communication-free parallel
    mode instead of re-executing.  The full mix is reported alongside."""
    d, triples = lubm_like(n_universities=2, depts_per_univ=2,
                           profs_per_dept=2, students_per_prof=2)
    rows = _bench_one_mix("instantiated", ["q1", "q7", "q12"], n_workers,
                          n_per_template, triples, d)
    rows += _bench_one_mix("all", None, n_workers, n_per_template,
                           triples, d)
    return rows


# --------------------------------------------------- ISSUE 4: sharded mesh
_SHARDED_ARTIFACT = "artifacts/sharded_queries.json"


def _sharded_child(out_path: str = _SHARDED_ARTIFACT, n_workers: int = 8,
                   n_per_template: int = 8, trials: int = 7,
                   n_devices: int = 8) -> None:
    """Runs inside the forced-8-device subprocess: batched workload
    throughput and comm accounting, mesh substrate vs single device."""
    from repro.core.substrate import MeshSubstrate

    import jax

    got = len(jax.devices())
    if got != n_devices:  # a pre-set XLA_FLAGS overrode the forced count
        raise RuntimeError(
            f"expected {n_devices} forced host devices, found {got}; "
            "the artifact would measure the wrong topology"
        )

    d, triples = lubm_like(n_universities=2, depts_per_univ=2,
                           profs_per_dept=2, students_per_prof=2)
    wl = Workload(d, seed=2)
    names = ["q1", "q7", "q12"]  # the instantiated (distributed-path) mix

    def workload():
        return [wl.templates[t].instantiate(wl.rng)
                for t in names for _ in range(n_per_template)]

    single = AdHashEngine(triples, n_workers, adaptive=False, capacity=64)
    mesh = AdHashEngine(triples, n_workers, adaptive=False, capacity=64,
                        substrate=MeshSubstrate())
    for _ in range(2):  # warm both paths past retry doublings
        single.query_batch(workload())
        mesh.query_batch(workload())

    n = len(names) * n_per_template
    single_trials, mesh_trials, recompiles = [], [], 0
    comm_single = comm_mesh = 0
    reps = 4  # lengthen each timed window past scheduler jitter
    for _ in range(trials):
        qs = workload()  # identical list for both engines per trial
        t0 = time.perf_counter()
        for _ in range(reps):
            res_s = single.query_batch(qs)
        single_trials.append((time.perf_counter() - t0) / reps)
        cache0 = be.probe_compile_cache_size()
        t0 = time.perf_counter()
        for _ in range(reps):
            res_m = mesh.query_batch(qs)
        mesh_trials.append((time.perf_counter() - t0) / reps)
        recompiles += be.probe_compile_cache_size() - cache0
        comm_single += sum(st.comm_cells for _, st in res_s)
        comm_mesh += sum(st.comm_cells for _, st in res_m)

    # median, not best-of: the 8-device collective rendezvous makes per-trial
    # times heavy-tailed on a shared host (occasional lucky-scheduling
    # outliers), and the CI perf gate needs a statistic that is stable
    # across runs, not the luckiest window
    out = {
        "n_devices": len(jax.devices()),
        "n_workers": n_workers,
        "n_queries_per_trial": n,
        "trials": trials,
        "single_qps": n / float(np.median(single_trials)),
        "sharded_qps": n / float(np.median(mesh_trials)),
        "comm_cells_single": comm_single,
        "comm_cells_sharded": comm_mesh,
        "post_warm_recompiles": recompiles,
    }
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(out, indent=2))


def run_sharded(n_devices: int = 8) -> list[tuple[str, float, str]]:
    """Mesh-substrate workload throughput vs single device (ISSUE 4).

    Spawns a subprocess with ``n_devices`` forced host devices (the flag
    must precede jax initialization), which writes the JSON artifact
    ``artifacts/sharded_queries.json``: queries/s and total comm cells for
    the sharded and single-device engines, plus post-warmup recompiles
    (must be zero).  Comm cells must match bit-for-bit — the collectives
    change where bytes move, not how many.

    The child runs on the CPU (``JAX_PLATFORMS=cpu`` in its own env): it
    rehearses the mesh on forced host devices, so its rows are CPU rows,
    and it never competes for an accelerator the parent holds."""
    root = Path(__file__).resolve().parent.parent
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        # appended last: XLA flag parsing is last-wins, so the forced count
        # beats any same flag already exported (the child asserts it took)
        "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                      f" --xla_force_host_platform_device_count={n_devices}"),
        "PYTHONPATH": os.pathsep.join(
            [str(root), str(root / "src"),
             os.environ.get("PYTHONPATH", "")]),
    }
    subprocess.run(
        [sys.executable, "-c",
         "from benchmarks.bench_queries import _sharded_child; "
         f"_sharded_child(n_devices={n_devices})"],
        check=True, cwd=str(root), env=env, timeout=900,
    )
    data = json.loads((root / _SHARDED_ARTIFACT).read_text())
    assert data["comm_cells_sharded"] == data["comm_cells_single"], data
    w = data["n_workers"]
    return [
        (f"sharded/w{w}d{data['n_devices']}/single_device_qps",
         data["single_qps"], f"n_queries={data['n_queries_per_trial']}"),
        (f"sharded/w{w}d{data['n_devices']}/sharded_qps",
         data["sharded_qps"],
         f"comm_cells={data['comm_cells_sharded']}"
         f" (=={data['comm_cells_single']} single)"),
        (f"sharded/w{w}d{data['n_devices']}/post_warm_recompiles",
         float(data["post_warm_recompiles"]), "must_be_zero"),
    ]


# ------------------------------------- ISSUE 9: main-index chain fast path
_STAR_ARTIFACT = "artifacts/subject_star_sharded.json"


def _subject_star_child(out_path: str = _STAR_ARTIFACT, n_workers: int = 8,
                        n_queries: int = 24, trials: int = 7,
                        n_devices: int = 8) -> None:
    """Runs inside the forced-8-device subprocess: subject-star (case-(i))
    queries over the main index, fused chain route vs the same engine with
    the chain disabled (the pre-ISSUE-9 distributed route)."""
    from repro.core.substrate import MeshSubstrate, trace_host_syncs

    import jax

    got = len(jax.devices())
    if got != n_devices:  # a pre-set XLA_FLAGS overrode the forced count
        raise RuntimeError(
            f"expected {n_devices} forced host devices, found {got}; "
            "the artifact would measure the wrong topology"
        )

    from repro.core.query import Const, Query, TriplePattern, Var

    d, triples = lubm_like(n_universities=4, depts_per_univ=3,
                           profs_per_dept=4, students_per_prof=6)
    wl = Workload(d, seed=5)
    kw = dict(adaptive=False, capacity=256, use_count_oracle=False)
    fast = AdHashEngine(triples, n_workers, substrate=MeshSubstrate(), **kw)
    dist = AdHashEngine(triples, n_workers, substrate=MeshSubstrate(),
                        local_chain=False, **kw)

    # a three-pattern subject star (the paper's case-(i) shape): anchored by
    # a takesCourse constant, extended by type + advisor.  The chain fuses
    # all three stages into one dispatch; the distributed route pays one
    # dispatch + one all-reduce + one host sync *per stage*.
    def star(course_id):
        x = Var("x")
        return Query([
            TriplePattern(x, Const(d.lookup("ub:takesCourse")),
                          Const(course_id)),
            TriplePattern(x, Const(d.lookup("rdf:type")),
                          Const(d.lookup("ub:Student"))),
            TriplePattern(x, Const(d.lookup("ub:advisor")), Var("y")),
        ], name="star3")

    courses = np.unique(triples[
        triples[:, 1] == d.lookup("ub:takesCourse"), 2])
    # one fixed workload: warm timing measures the *route* (dispatches,
    # syncs, collectives), not per-fresh-constant planning — the planner
    # memo serves repeats on both engines identically
    qs = [star(int(c))
          for c in wl.rng.choice(courses, size=n_queries, replace=False)]
    for _ in range(2):  # warm: compile + settle capacity classes + plans
        for q in qs:
            fast.query(q)
            dist.query(q)

    fast_trials, dist_trials, recompiles = [], [], 0
    routes = set()
    for _ in range(trials):
        cache0 = be.probe_compile_cache_size()
        t0 = time.perf_counter()
        for q in qs:
            _, st = fast.query(q)
            routes.add(st.route)
        fast_trials.append(time.perf_counter() - t0)
        recompiles += be.probe_compile_cache_size() - cache0
        t0 = time.perf_counter()
        for q in qs:
            dist.query(q)
        dist_trials.append(time.perf_counter() - t0)
    if routes != {"mesh-local-main"}:
        raise RuntimeError(f"star workload left the chain route: {routes}")

    # the one-sync invariant, on a fully warm repeated query (plan memo hit)
    q = qs[0]
    fast.query(q)
    with trace_host_syncs() as tr:
        for _ in range(8):
            fast.query(q)
    syncs_per_query = tr.host_transfers / 8.0

    # median, not best-of (see _sharded_child)
    out = {
        "n_devices": got,
        "n_workers": n_workers,
        "n_queries_per_trial": n_queries,
        "trials": trials,
        "local_main_qps": n_queries / float(np.median(fast_trials)),
        "distributed_qps": n_queries / float(np.median(dist_trials)),
        "host_syncs_per_query": syncs_per_query,
        "post_warm_recompiles": recompiles,
    }
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(out, indent=2))


def run_subject_star_sharded(n_devices: int = 8
                             ) -> list[tuple[str, float, str]]:
    """Main-index subject-star fast path vs distributed route (ISSUE 9).

    Spawns the forced-8-device subprocess, which writes
    ``artifacts/subject_star_sharded.json``: queries/s on the fused
    zero-collective ``mesh-local-main`` route vs the same queries with the
    chain disabled, host syncs per warm query (must be exactly 1) and
    post-warmup recompiles (must be zero).

    The child runs on the CPU (``JAX_PLATFORMS=cpu`` in its own env): it
    rehearses the mesh on forced host devices, so its rows are CPU rows,
    and it never competes for an accelerator the parent holds."""
    root = Path(__file__).resolve().parent.parent
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        # appended last: XLA flag parsing is last-wins (child asserts)
        "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                      f" --xla_force_host_platform_device_count={n_devices}"),
        "PYTHONPATH": os.pathsep.join(
            [str(root), str(root / "src"),
             os.environ.get("PYTHONPATH", "")]),
    }
    subprocess.run(
        [sys.executable, "-c",
         "from benchmarks.bench_queries import _subject_star_child; "
         f"_subject_star_child(n_devices={n_devices})"],
        check=True, cwd=str(root), env=env, timeout=900,
    )
    data = json.loads((root / _STAR_ARTIFACT).read_text())
    assert data["host_syncs_per_query"] == 1.0, data
    w = data["n_workers"]
    tag = f"star/w{w}d{data['n_devices']}"
    return [
        (f"{tag}/local_main_qps", data["local_main_qps"],
         f"n_queries={data['n_queries_per_trial']}"),
        (f"{tag}/distributed_qps", data["distributed_qps"],
         "chain disabled (pre-change route)"),
        (f"{tag}/speedup_x",
         data["local_main_qps"] / data["distributed_qps"],
         "local_main vs distributed"),
        (f"{tag}/host_syncs_per_query", data["host_syncs_per_query"],
         "must_be_one"),
        (f"{tag}/post_warm_recompiles",
         float(data["post_warm_recompiles"]), "must_be_zero"),
    ]


if __name__ == "__main__":
    for r in (run() + run_batched() + run_sharded()
              + run_subject_star_sharded()):
        print(",".join(map(str, r)))
