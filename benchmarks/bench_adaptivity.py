"""Paper Figs 12-15: adaptivity under workload, threshold sensitivity, and
the static-representative-workload comparison.

Fig 13/14: cumulative execution time + communication with and without
adaptivity as the workload shifts template every K queries.
Fig 12: frequency-threshold sweep (time / comm / replication).
Fig 15: training on a category mix then testing on the full mix (static
workload-based partitioning emulation) vs adapting online.

``run_parallel_mode_sharded`` (ISSUE 5) is the adaptivity payoff measured
*on the mesh*: post-redistribution PI-hit queries through the shard-local
route (zero collectives) vs the same queries through the distributed
all_to_all path, under 8 forced host devices — the "adapt, then stop
communicating" number, persisted to ``artifacts/parallel_mode_sharded.json``
and gated in CI by ``benchmarks/compare.py``.
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
from repro.core.engine import AdHashEngine
from repro.data.synthetic_rdf import Workload, lubm_like


def _phase_workload(wl: Workload, order: list[str], per_phase: int):
    qs = []
    for name in order:
        for _ in range(per_phase):
            qs.append(wl.templates[name].instantiate(wl.rng))
    return qs


def run(n_workers: int = 8) -> list[tuple[str, float, str]]:
    d, triples = lubm_like(n_universities=4, depts_per_univ=3,
                           profs_per_dept=4, students_per_prof=6)
    rows = []
    order = ["q1", "q12", "q7", "q2"]
    per_phase = 30  # IRD pays upfront; the crossover needs amortization
    # (paper: "AdHash incurs more communication at the beginning because of
    #  the IRD process.  However, it then converges" — Fig 15 discussion)

    # ------------------------------ Fig 13/14: shifting workload, AD vs NA
    for adaptive in (False, True):
        wl = Workload(d, seed=3)
        eng = AdHashEngine(triples, n_workers, adaptive=adaptive,
                           frequency_threshold=4)
        qs = _phase_workload(wl, order, per_phase)
        t0 = time.perf_counter()
        for q in qs:
            eng.query(q)
        dt = (time.perf_counter() - t0) * 1e6 / len(qs)
        tag = "adhash" if adaptive else "adhash_na"
        comm = eng.report.comm_cells + eng.report.ird_comm_cells
        rows.append(
            (f"fig13/{tag}_us_per_query", dt,
             f"comm_cells={comm} redistributions={eng.report.n_redistributions}"
             f" parallel_frac="
             f"{(eng.report.n_parallel + eng.report.n_parallel_replica) / eng.report.n_queries:.2f}")
        )
    # adapted engine must communicate less overall (Fig 13b)
    comm_na = int(rows[-2][2].split("comm_cells=")[1].split(" ")[0])
    comm_ad = int(rows[-1][2].split("comm_cells=")[1].split(" ")[0])
    assert comm_ad < comm_na, (comm_ad, comm_na)

    # --------------------------------- Fig 12: frequency threshold sweep
    for thresh in (1, 4, 10, 30):
        wl = Workload(d, seed=4)
        eng = AdHashEngine(triples, n_workers, adaptive=True,
                           frequency_threshold=thresh)
        qs = _phase_workload(wl, order, per_phase)
        t0 = time.perf_counter()
        for q in qs:
            eng.query(q)
        dt = (time.perf_counter() - t0) * 1e6 / len(qs)
        rows.append(
            (f"fig12/threshold{thresh}_us", dt,
             f"comm_cells={eng.report.comm_cells + eng.report.ird_comm_cells}"
             f" replication={eng.replication_ratio():.3f}")
        )

    # ----------------------- Fig 15: static training mix vs online adapting
    test_wl = Workload(d, seed=5)
    test_qs = _phase_workload(test_wl, order, 6)
    for train_mix in (["q1", "q12"], ["q7", "q2"], None):
        wl = Workload(d, seed=6)
        eng = AdHashEngine(triples, n_workers, adaptive=True,
                           frequency_threshold=3)
        if train_mix is not None:
            for name in train_mix:
                for _ in range(8):
                    eng.query(wl.templates[name].instantiate(wl.rng))
            eng.adaptive = False  # freeze: static workload-based partitioning
        t0 = time.perf_counter()
        comm0 = eng.report.comm_cells
        for q in test_qs:
            eng.query(q)
        dt = (time.perf_counter() - t0) * 1e6 / len(test_qs)
        tag = "+".join(train_mix) if train_mix else "online"
        rows.append((f"fig15/{tag}_us", dt,
                     f"test_comm={eng.report.comm_cells - comm0}"))
    return rows


# ----------------------------------- ISSUE 5: parallel mode on the mesh
_PARALLEL_ARTIFACT = "artifacts/parallel_mode_sharded.json"


def _parallel_mode_child(out_path: str = _PARALLEL_ARTIFACT,
                         n_workers: int = 8, n_devices: int = 8,
                         n_repeat: int = 24, trials: int = 5) -> None:
    """Runs inside the forced-8-device subprocess: PI-hit (shard-local
    parallel-mode) throughput vs the distributed all_to_all path for the
    same queries on the same mesh."""
    import jax

    from repro.core.substrate import MeshSubstrate

    got = len(jax.devices())
    if got != n_devices:  # a pre-set XLA_FLAGS overrode the forced count
        raise RuntimeError(
            f"expected {n_devices} forced host devices, found {got}; "
            "the artifact would measure the wrong topology"
        )

    d, triples = lubm_like(n_universities=2, depts_per_univ=2,
                           profs_per_dept=2, students_per_prof=2)
    wl = Workload(d, seed=9)

    # the distributed engine doubles as a probe: keep queries that genuinely
    # take the communicating path (mode distributed, wire cells > 0) — the
    # comparison is all_to_all vs no-collective, not local vs local
    dist = AdHashEngine(triples, n_workers, adaptive=False, capacity=256,
                        substrate=MeshSubstrate())
    base = []
    for q in wl.sample(8):
        _, st = dist.query(q)
        if st.mode == "distributed" and st.comm_cells > 0:
            base.append(q)
    base = base[:4]
    if not base:
        raise RuntimeError("workload sample produced no distributed queries")

    # adapt: repeated exact queries heat the map, IRD redistributes, and the
    # stream settles into PI hits on the shard-local route
    par = AdHashEngine(triples, n_workers, adaptive=True,
                       frequency_threshold=2, capacity=256,
                       substrate=MeshSubstrate())
    for _ in range(3):
        settled = [par.query(q) for q in base]
    modes = {st.mode for _, st in settled}
    routes = {st.route for _, st in settled}
    comm_parallel = sum(st.comm_cells for _, st in settled)
    if modes != {"parallel-replica"} or routes != {"mesh-local"}:
        raise RuntimeError(
            f"stream did not settle into shard-local parallel mode: "
            f"modes={modes} routes={routes}"
        )
    for _ in range(2):  # warm the distributed engine past retry doublings
        for q in base:
            dist.query(q)

    n = len(base) * n_repeat

    def timed(eng) -> float:
        t0 = time.perf_counter()
        for _ in range(n_repeat):
            for q in base:
                eng.query(q)
        return time.perf_counter() - t0

    # interleave the two engines' trials so background-load drift hits both
    # paths alike; trials are sized (n_repeat) so one trial spans hundreds
    # of milliseconds even on the fast path — parallel mode is dispatch-
    # latency-bound, and sub-jitter-length windows made its qps flap ~25%
    # run-to-run on a shared host
    comm0 = dist.report.comm_cells
    par_trials, dist_trials = [], []
    for _ in range(trials):
        par_trials.append(timed(par))
        dist_trials.append(timed(dist))
    comm_distributed = dist.report.comm_cells - comm0

    # median, not best-of: stable across runs under shared-host scheduling
    # jitter (the CI gate diffs these numbers against a checked-in
    # baseline).  The speedup is the median of *paired* per-trial ratios:
    # each pair ran back to back, so a load spike spanning one pair inflates
    # both of its timings and cancels in the ratio, where a ratio of
    # whole-run aggregates would absorb the spike into only one side.
    out = {
        "n_devices": n_devices,
        "n_workers": n_workers,
        "n_queries_per_trial": n,
        "trials": trials,
        "parallel_mode_qps": n / float(np.median(par_trials)),
        "distributed_qps": n / float(np.median(dist_trials)),
        "speedup_x": float(np.median(
            [d / p for d, p in zip(dist_trials, par_trials)]
        )),
        "comm_cells_parallel": comm_parallel,
        "comm_cells_distributed": comm_distributed,
        "n_redistributions": par.report.n_redistributions,
    }
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(out, indent=2))


def run_parallel_mode_sharded(n_devices: int = 8
                              ) -> list[tuple[str, float, str]]:
    """Adaptivity payoff on the mesh (ISSUE 5 acceptance): after IRD, PI-hit
    queries on the shard-local route must sustain >= 2x the throughput of
    the same queries on the distributed all_to_all path, with zero wire
    cells.  Spawns the forced-8-device subprocess and reads back
    ``artifacts/parallel_mode_sharded.json``.

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
         "from benchmarks.bench_adaptivity import _parallel_mode_child; "
         f"_parallel_mode_child(n_devices={n_devices})"],
        check=True, cwd=str(root), env=env, timeout=900,
    )
    data = json.loads((root / _PARALLEL_ARTIFACT).read_text())
    # adapted execution is literally communication-free on the mesh, and
    # dropping the collectives must be worth at least 2x
    assert data["comm_cells_parallel"] == 0, data
    assert data["comm_cells_distributed"] > 0, data
    assert data["speedup_x"] >= 2.0, data
    w, dv = data["n_workers"], data["n_devices"]
    return [
        (f"parallel_mode/w{w}d{dv}/parallel_mode_qps",
         data["parallel_mode_qps"],
         f"comm_cells={data['comm_cells_parallel']} route=mesh-local"),
        (f"parallel_mode/w{w}d{dv}/distributed_qps",
         data["distributed_qps"],
         f"comm_cells={data['comm_cells_distributed']}"),
        (f"parallel_mode/w{w}d{dv}/speedup_x", data["speedup_x"],
         "must_be_ge_2"),
    ]


if __name__ == "__main__":
    for r in run() + run_parallel_mode_sharded():
        print(",".join(map(str, r)))
