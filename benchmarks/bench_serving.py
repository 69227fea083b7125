"""ISSUE 8: online-serving benchmark — latency, saturation, shed behaviour.

``run_serving_sharded`` drives the :mod:`repro.serving` front-end on a real
8-device mesh (the bench_recovery subprocess pattern) with an open-loop
Zipf-over-templates arrival stream and reports:

  * ``saturation_qps`` — measured closed-burst throughput: every request
    arrives at once into an unbounded admission window and the virtual
    clock is charged real wall seconds (measured mode), so the makespan is
    the real cost of the serving path end to end.  Gated (normalized) —
    a drop means the serve loop, batcher, or engine got slower.
  * ``shed_frac_x`` — deterministic shed fraction at 2x modeled saturation
    on a fresh engine (virtual clock + fixed service model, the DES regime
    of the acceptance tests).  Hardware-independent, gated *lower-is-
    better*: an increase means admission/shedding got more aggressive or
    continuous batching lost throughput.
  * ``p50_ms`` / ``p99_ms`` — measured admitted latency at ~0.5x the
    measured saturation rate.  Informational (wall-clock noise), the SLO
    story is gated by the deterministic rows and the serving test suite.

Zero post-warmup recompiles across the measured legs ride in the derived
text (``post_warm_recompiles=N``) and gate at zero: a warmed serve loop
must run entirely from the compile cache.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

_ARTIFACT = "artifacts/serving.json"

# Zipf-over-templates popularity (weight 1/rank over the five LUBM
# templates): a skewed mix keeps some shape buckets hot and others sparse,
# which is exactly the regime continuous batching has to handle
_ZIPF_MIX = {"q1": 1.0, "q2": 1 / 2, "q7": 1 / 3, "q9": 1 / 4, "q12": 1 / 5}


def _serving_child(out_path: str = _ARTIFACT, n_workers: int = 8,
                   n_devices: int = 8) -> None:
    """Runs inside the forced-8-device subprocess."""
    import jax

    import repro.core  # noqa: F401
    from repro.core.backend import probe_compile_cache_size
    from repro.core.engine import AdHashEngine
    from repro.core.substrate import MeshSubstrate
    from repro.data.synthetic_rdf import Workload, lubm_like
    from repro.runtime.fault_injection import VirtualClock
    from repro.serving import (ServeConfig, ServeLoop, open_loop_arrivals,
                               replay_open_loop)

    got = len(jax.devices())
    if got != n_devices:
        raise RuntimeError(
            f"expected {n_devices} forced host devices, found {got}"
        )

    d, triples = lubm_like(n_universities=2, depts_per_univ=2,
                           profs_per_dept=2, students_per_prof=2)
    wl = Workload(d, mix=_ZIPF_MIX, seed=13)
    kw = dict(adaptive=True, frequency_threshold=2, capacity=256)
    no_brownout = dict(brownout_enter=(9.0, 10.0), brownout_exit=(8.0, 9.0))

    def serve(eng, queries, rate, slo, service_model=None, **cfg):
        loop = ServeLoop(
            eng,
            ServeConfig(slo_s=slo, batch_target=4, **cfg),
            clock=VirtualClock(), service_model=service_model)
        arr = open_loop_arrivals(queries, rate_qps=rate, seed=13)
        replay_open_loop(loop, arr)
        return loop

    # ---- warm: two full streams converge the adaptivity state (pass 1
    # indexes hot patterns, pass 2 runs them through the PI-hit paths) and
    # populate the compile cache for every shape the workload produces
    eng = AdHashEngine(triples, n_workers, substrate=MeshSubstrate(), **kw)
    qs_sat = wl.sample(200)
    burst = dict(slo=1e6, queue_bound=len(qs_sat) + 1,
                 bucket_window=64, **no_brownout)
    for _ in range(2):
        serve(eng, qs_sat, rate=1e9, **burst)
    cache_warm = probe_compile_cache_size()

    # ---- saturation leg (measured): all 200 requests arrive at once, the
    # virtual clock is charged real wall seconds, makespan == real cost
    loop_s = serve(eng, qs_sat, rate=1e9, **burst)
    rs = loop_s.report
    assert rs.answered == len(qs_sat) and rs.shed == 0 and rs.rejected == 0
    saturation_qps = len(qs_sat) / loop_s.clock.now()

    # ---- latency leg (measured): ~0.5x the measured saturation rate
    qs_lat = wl.sample(120)
    slo_lat = max(0.05, 40.0 / saturation_qps)
    loop_l = serve(eng, qs_lat, rate=0.5 * saturation_qps, slo=slo_lat,
                   queue_bound=64, bucket_window=32, **no_brownout)
    rl = loop_l.report
    assert rl.answered > 0

    post_warm_recompiles = probe_compile_cache_size() - cache_warm

    # ---- overload leg (modeled, deterministic): fresh engine, fixed
    # service model, 2x modeled saturation (batch_target / svc = 200 qps)
    # — the virtual-clock DES of the acceptance tests, so shed_frac is
    # bit-reproducible across machines
    eng2 = AdHashEngine(triples, n_workers, substrate=MeshSubstrate(), **kw)
    qs_over = wl.sample(150)
    loop_o = serve(eng2, qs_over, rate=400.0, slo=0.2,
                   service_model=lambda n: 0.02,
                   queue_bound=16, bucket_window=16)
    ro = loop_o.report
    assert ro.answered > 0 and ro.shed > 0
    assert ro.p99_s <= 0.2 + 1e-9, (ro.p99_s,)

    data = {
        "n_workers": n_workers,
        "n_devices": n_devices,
        "n_saturation": len(qs_sat),
        "saturation_qps": saturation_qps,
        "latency_rate_qps": 0.5 * saturation_qps,
        "p50_ms": rl.p50_s * 1e3,
        "p99_ms": rl.p99_s * 1e3,
        "latency_answered": rl.answered,
        "shed_frac": ro.shed_rate,
        "overload_answered": ro.answered,
        "overload_shed": ro.shed,
        "overload_rejected": ro.rejected,
        "overload_p99_s": ro.p99_s,
        "post_warm_recompiles": post_warm_recompiles,
    }
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(data, indent=2))


def run_serving_sharded(n_devices: int = 8) -> list[tuple[str, float, str]]:
    """ISSUE 8 serving rows on the mesh: measured saturation throughput and
    p50/p99, plus the deterministic 2x-overload shed fraction.

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
         "from benchmarks.bench_serving import _serving_child; "
         f"_serving_child(n_devices={n_devices})"],
        check=True, cwd=str(root), env=env, timeout=900,
    )
    data = json.loads((root / _ARTIFACT).read_text())
    assert data["post_warm_recompiles"] == 0, data
    assert data["overload_shed"] > 0, data
    assert data["overload_p99_s"] <= 0.2 + 1e-9, data
    tag = f"serving/w{data['n_workers']}d{data['n_devices']}"
    return [
        (f"{tag}/saturation_qps", data["saturation_qps"],
         f"measured closed-burst drain, n={data['n_saturation']}"
         f" post_warm_recompiles={data['post_warm_recompiles']}"),
        (f"{tag}/shed_frac_x", data["shed_frac"],
         "deterministic 2x-overload shed fraction (lower is better), "
         f"answered={data['overload_answered']}"
         f" shed={data['overload_shed']}"
         f" rejected={data['overload_rejected']}"
         f" admitted_p99_s={data['overload_p99_s']:.3f}"),
        (f"{tag}/p50_ms", data["p50_ms"],
         f"measured @ {data['latency_rate_qps']:.0f} qps"
         " (~0.5x saturation), informational"),
        (f"{tag}/p99_ms", data["p99_ms"],
         f"measured @ {data['latency_rate_qps']:.0f} qps"
         " (~0.5x saturation), informational"),
    ]


if __name__ == "__main__":
    for r in run_serving_sharded():
        print(",".join(map(str, r)))
