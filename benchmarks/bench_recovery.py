"""ISSUE 7: recovery benchmark — what worker loss and master restart cost.

``run_recovery_sharded`` runs the failure episode on a real 8-device mesh
(the bench_balance subprocess pattern) and reports:

  * ``healthy_qps`` / ``degraded_qps`` — the same PI-hit workload through
    the zero-collective ``mesh-local`` route vs the demoted distributed
    route while one shard is down (answers asserted bit-identical, routes
    asserted per phase);
  * ``degraded_retain_x`` — paired-median degraded/healthy throughput
    ratio: the fraction of throughput the engine *retains* while degraded
    (hardware-portable, gates un-normalized — a drop means degraded mode
    got slower relative to healthy);
  * ``replay_qps`` — master-recovery speed: query-log replay throughput to
    PI-fingerprint parity, with ``time_to_first_answer_us`` (full
    ``recover_master`` from the snapshot: engine bootstrap + adaptivity
    restore + first answered query) riding in the derived text.

Zero post-warmup recompiles across the kill/degrade/recover episode is part
of the gate (``post_warm_recompiles=0`` in the derived text): failure
handling must not invalidate the compile cache.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

_ARTIFACT = "artifacts/recovery.json"


def _recovery_child(out_path: str = _ARTIFACT, n_workers: int = 8,
                    n_devices: int = 8, n_repeat: int = 3,
                    trials: int = 5) -> None:
    """Runs inside the forced-8-device subprocess."""
    import jax

    import repro.core  # noqa: F401
    from repro.core.backend import probe_compile_cache_size
    from repro.core.engine import AdHashEngine
    from repro.core.substrate import MeshSubstrate
    from repro.checkpoint.checkpoint import CheckpointManager
    from repro.data.synthetic_rdf import Workload, lubm_like
    from repro.runtime.fault_injection import FaultInjector
    from repro.runtime.fault_tolerance import (
        HeartbeatMonitor,
        recover_master,
        replay_query_log,
    )

    got = len(jax.devices())
    if got != n_devices:
        raise RuntimeError(
            f"expected {n_devices} forced host devices, found {got}"
        )

    d, triples = lubm_like(n_universities=4, depts_per_univ=3,
                           profs_per_dept=4, students_per_prof=6)
    wl = Workload(d, seed=11)
    qs = wl.sample(12)
    kw = dict(adaptive=True, frequency_threshold=2, capacity=256)
    eng = AdHashEngine(triples, n_workers, substrate=MeshSubstrate(), **kw)

    def answers(rel, q):
        return set(map(tuple, rel.project_to(q.vars)))

    # warm past IRD (pass 2) and through the first PI-hit execution of
    # every pattern (pass 3); the log records each query the engine sees,
    # in order — replay parity depends on it
    log = []
    for q in qs * 3:
        eng.query(q)
        log.append(q)
    cache_warm = probe_compile_cache_size()

    mon = HeartbeatMonitor(n_workers, timeout_s=5.0, now=0.0)
    inj = FaultInjector(eng, mon)
    inj.tick(1.0)

    def timed(expect_route: str) -> float:
        t0 = time.perf_counter()
        for _ in range(n_repeat):
            for q in qs:
                rel, st = eng.query(q)
                log.append(q)
                assert st.route == expect_route, (st.route, expect_route)
        return time.perf_counter() - t0

    # reference answers, healthy (indexed: workload names are templates
    # and repeat across different constant bindings)
    ref = [answers(eng.query(q)[0], q) for q in qs]
    log.extend(qs)

    # interleaved paired trials: healthy (mesh-local) vs one shard down
    # (mesh-degraded), the bench_balance discipline
    healthy_trials, degraded_trials = [], []
    for _ in range(trials):
        healthy_trials.append(timed("mesh-local"))
        inj.kill(3)
        inj.tick(11.0)  # cross the detector deadline
        degraded_trials.append(timed("mesh-degraded"))
        inj.restart(3)

    # answers bit-identical on the degraded route
    inj.kill(3)
    inj.tick(11.0)
    for i, q in enumerate(qs):
        rel, st = eng.query(q)
        log.append(q)
        assert st.route == "mesh-degraded", st.route
        assert answers(rel, q) == ref[i], (i, q.name)
    inj.restart(3)
    rel, st = eng.query(qs[0])
    log.append(qs[0])
    assert st.route == "mesh-local", st.route

    episode_recompiles = probe_compile_cache_size() - cache_warm

    # ---- master recovery: snapshot + restore, and log-replay to parity
    ckpt_dir = Path(out_path).parent / "recovery_ckpt"
    mgr = CheckpointManager(str(ckpt_dir))
    mgr.save_engine_state(eng, log)
    mgr.save_adaptivity(eng, step=1)
    fp = eng.pattern_index.fingerprint()

    t0 = time.perf_counter()
    rec = recover_master(mgr, triples, n_workers, substrate=MeshSubstrate(),
                         **kw)
    # the snapshot covers the whole log: PI parity with zero replay
    # (checked before the first query — a PI hit ticks the LRU clock)
    assert rec.pattern_index.fingerprint() == fp
    rel, st = rec.query(qs[0])
    time_to_first_answer = time.perf_counter() - t0
    assert st.route == "mesh-local", st.route
    assert answers(rel, qs[0]) == ref[0]

    # pay-as-you-go path: no snapshot, pure log replay to PI parity
    fresh = AdHashEngine(triples, n_workers, substrate=MeshSubstrate(), **kw)
    t0 = time.perf_counter()
    replay_query_log(fresh, mgr.load_query_log())
    replay_s = time.perf_counter() - t0
    assert fresh.pattern_index.fingerprint() == fp

    recovery_recompiles = probe_compile_cache_size() - cache_warm \
        - episode_recompiles

    n = len(qs) * n_repeat
    data = {
        "n_workers": n_workers,
        "n_devices": n_devices,
        "n_queries_per_trial": n,
        "trials": trials,
        "healthy_qps": n / float(np.median(healthy_trials)),
        "degraded_qps": n / float(np.median(degraded_trials)),
        # paired-median throughput fraction retained while degraded: the
        # trials are wall times, so qps_d / qps_h == t_h / t_d
        "degraded_retain": float(np.median(
            [th / td for th, td in zip(healthy_trials, degraded_trials)]
        )),
        "n_degraded": eng.report.n_degraded,
        "replay_qps": len(log) / replay_s,
        "n_replayed": len(log),
        "time_to_first_answer_us": time_to_first_answer * 1e6,
        "pi_parity": 1,
        "episode_recompiles": episode_recompiles,
        "recovery_recompiles": recovery_recompiles,
    }
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(data, indent=2))


def run_recovery_sharded(n_devices: int = 8) -> list[tuple[str, float, str]]:
    """ISSUE 7 acceptance on the mesh: one shard failed mid-workload keeps
    every answer bit-identical over the demoted route with zero recompiles,
    and a restarted master replays to PI-fingerprint parity.

    The child runs on the CPU (``JAX_PLATFORMS=cpu`` in its own env): it
    rehearses the mesh on forced host devices, so its rows are CPU rows,
    and it never competes for an accelerator the parent holds."""
    root = Path(__file__).resolve().parent.parent
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                      f" --xla_force_host_platform_device_count={n_devices}"),
        "PYTHONPATH": os.pathsep.join(
            [str(root), str(root / "src"),
             os.environ.get("PYTHONPATH", "")]),
    }
    subprocess.run(
        [sys.executable, "-c",
         "from benchmarks.bench_recovery import _recovery_child; "
         f"_recovery_child(n_devices={n_devices})"],
        check=True, cwd=str(root), env=env, timeout=900,
    )
    data = json.loads((root / _ARTIFACT).read_text())
    assert data["pi_parity"] == 1, data
    assert data["episode_recompiles"] == 0, data
    assert data["recovery_recompiles"] == 0, data
    assert data["n_degraded"] > 0, data
    # degraded mode must stay usable: paying the distributed route is fine,
    # falling off a cliff (<5% of healthy throughput) is not
    assert data["degraded_retain"] > 0.05, data
    tag = f"recovery/w{data['n_workers']}d{data['n_devices']}"
    return [
        (f"{tag}/healthy_qps", data["healthy_qps"],
         f"mesh-local route, post_warm_recompiles={data['episode_recompiles']}"),
        (f"{tag}/degraded_qps", data["degraded_qps"],
         f"mesh-degraded route, n_degraded={data['n_degraded']}"),
        (f"{tag}/degraded_retain_x", data["degraded_retain"],
         "fraction of healthy throughput retained while degraded, "
         "paired-median"),
        (f"{tag}/replay_qps", data["replay_qps"],
         f"n_replayed={data['n_replayed']} pi_parity={data['pi_parity']}"
         f" time_to_first_answer_us={data['time_to_first_answer_us']:.0f}"
         f" post_warm_recompiles={data['recovery_recompiles']}"),
    ]


if __name__ == "__main__":
    for r in run_recovery_sharded():
        print(",".join(map(str, r)))
