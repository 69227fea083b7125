"""Paper Table 15 + Fig 17: data + work balance across workers after an
adaptive workload (initial hash partitioning AND IRD placement).

``run_skew`` / ``run_skew_sharded`` (ISSUE 6) measure the placement layer's
skew resistance: a Zipf-hot workload over a hub-subject dataset, hash
placement vs a directory placement whose rebalance hook splits the hub
across shards.  Gated rows: qps for both policies, the paired speedup
ratio, and the max/mean shard-load improvement factor (both ``_x`` rows are
hardware-portable and gate un-normalized in benchmarks/compare.py)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

import repro.core  # noqa: F401
from repro.core.backend import probe_compile_cache_size
from repro.core.engine import AdHashEngine
from repro.data.synthetic_rdf import Workload, lubm_like, zipf_skew, \
    zipf_workload


def run(n_workers: int = 8) -> list[tuple[str, float, str]]:
    d, triples = lubm_like(n_universities=4, depts_per_univ=3,
                           profs_per_dept=4, students_per_prof=6)
    eng = AdHashEngine(triples, n_workers, adaptive=True,
                       frequency_threshold=3)
    wl = Workload(d, seed=11)
    t0 = time.perf_counter()
    for q in wl.sample(40):
        eng.query(q)
    dt = (time.perf_counter() - t0) * 1e6 / 40

    lb = eng.load_balance()
    pct = 100.0 / max(lb["mean"] * n_workers, 1)
    rows = [
        (
            "table15/balance_us", dt,
            f"max%={lb['max'] * pct:.2f} min%={lb['min'] * pct:.2f}"
            f" std={lb['std']:.1f} replication={lb['replication_ratio']:.3f}",
        )
    ]
    # the paper's claim: near-uniform shares (max close to min)
    assert lb["max"] < 2.5 * max(lb["min"], 1), lb
    return rows


# --------------------------------- ISSUE 6: hot-key skew, hash vs directory
_SKEW_ARTIFACT = "artifacts/skew_sharded.json"


def _skew_engines(n_workers: int, substrate=None):
    """One engine per placement policy over the same Zipf-hub dataset.

    The count oracle is off so capacity hints are retry-discovered per
    worker — the whole point is that hash placement needs the hub star's
    full per-shard capacity class while the split placement works in one
    ~1/f as large; a global-count hint would hand both engines the same
    inflated class and erase the measurable difference.  IRD is disabled
    (huge threshold) to isolate the placement effect.

    Scenario shape: exponent 1.8 over 1024 subjects puts >half the triples
    on a handful of hub stars (rank-1 alone ~50%), and only 4 predicates
    keeps each (s, p) probe star a quarter of the whole hub — large enough
    that the hash engine's padded result capacity class, not fixed dispatch
    overhead, dominates query cost.  The wide object space keeps the stars
    dense after RDF set-dedupe.  The aggressive 1.2 skew threshold lets the
    directory engine cascade splits down the hub ranks instead of stopping
    after the first one."""
    triples = zipf_skew(n_subjects=1024, n_triples=800_000,
                        n_objects=1 << 21, n_predicates=4, exponent=1.8,
                        seed=0)
    common = dict(
        adaptive=True, frequency_threshold=10**9, capacity=256,
        use_count_oracle=False, substrate=substrate, skew_threshold=1.2,
    )
    hash_eng = AdHashEngine(triples, n_workers, placement="hash", **common)
    dir_eng = AdHashEngine(triples, n_workers, placement="directory",
                           **common)
    queries = zipf_workload(48, n_subjects=1024, n_predicates=4,
                            exponent=1.8, seed=1)
    return hash_eng, dir_eng, queries


def _skew_measure(n_workers: int, substrate=None, n_repeat: int = 8,
                  trials: int = 5) -> dict:
    hash_eng, dir_eng, queries = _skew_engines(n_workers, substrate)

    # The workload runs through query_batch: the star probes share one
    # shape bucket, so per-query python/dispatch overhead amortizes across
    # the batch and what remains is the padded data-plane work — which is
    # exactly where the two policies differ (the hash engine's bucket
    # carries the hub star's capacity class for *every* member, the
    # directory engine's a ~1/f class).  Warmup runs the batch twice per
    # engine: past retry-doubling discovery and past the directory
    # engine's skew-triggered rebalance (hub splits + store move).
    for eng in (hash_eng, dir_eng):
        eng.query_batch(queries)
        eng.query_batch(queries)
    cache_warm = probe_compile_cache_size()

    n = len(queries) * n_repeat

    def timed(eng) -> float:
        t0 = time.perf_counter()
        for _ in range(n_repeat):
            eng.query_batch(queries)
        return time.perf_counter() - t0

    # interleaved trials + median of paired ratios: same discipline as
    # bench_adaptivity (shared-host jitter hits both paths alike)
    hash_trials, dir_trials = [], []
    for _ in range(trials):
        hash_trials.append(timed(hash_eng))
        dir_trials.append(timed(dir_eng))

    hb = hash_eng.load_balance()
    db = dir_eng.load_balance()
    hash_ratio = hb["max"] / max(hb["mean"], 1e-9)
    dir_ratio = db["max"] / max(db["mean"], 1e-9)
    return {
        "n_workers": n_workers,
        "n_queries_per_trial": n,
        "trials": trials,
        "hash_qps": n / float(np.median(hash_trials)),
        "directory_qps": n / float(np.median(dir_trials)),
        "speedup_x": float(np.median(
            [h / d for h, d in zip(hash_trials, dir_trials)]
        )),
        "hash_max_over_mean": float(hash_ratio),
        "directory_max_over_mean": float(dir_ratio),
        "balance_x": float(hash_ratio / max(dir_ratio, 1e-9)),
        "n_rebalances": dir_eng.report.n_rebalances,
        "rebalance_comm_cells": dir_eng.report.rebalance_comm_cells,
        "n_splits": len(getattr(dir_eng.placement, "entries", {})),
        "post_warm_recompiles": probe_compile_cache_size() - cache_warm,
    }


def _skew_rows(data: dict, tag: str) -> list[tuple[str, float, str]]:
    return [
        (f"{tag}/hash_qps", data["hash_qps"],
         f"max_over_mean={data['hash_max_over_mean']:.2f}"),
        (f"{tag}/directory_qps", data["directory_qps"],
         f"max_over_mean={data['directory_max_over_mean']:.2f}"
         f" splits={data['n_splits']}"
         f" rebalances={data['n_rebalances']}"
         f" post_warm_recompiles={data['post_warm_recompiles']}"),
        (f"{tag}/speedup_x", data["speedup_x"], "directory vs hash qps"),
        (f"{tag}/balance_x", data["balance_x"],
         "max/mean load ratio improvement, hash vs directory"),
    ]


def run_skew(n_workers: int = 8) -> list[tuple[str, float, str]]:
    """In-process skew bench (single-device substrate, 8 logical workers)."""
    data = _skew_measure(n_workers)
    assert data["n_rebalances"] >= 1, data
    assert data["post_warm_recompiles"] == 0, data
    return _skew_rows(data, f"skew/w{n_workers}")


def _skew_sharded_child(out_path: str = _SKEW_ARTIFACT, n_workers: int = 8,
                        n_devices: int = 8) -> None:
    """Runs inside the forced-8-device subprocess: the same measurement with
    every stage under shard_map (the exception table rides into the bodies
    as a replicated operand; destinations cross real device boundaries)."""
    import jax

    from repro.core.substrate import MeshSubstrate

    got = len(jax.devices())
    if got != n_devices:
        raise RuntimeError(
            f"expected {n_devices} forced host devices, found {got}"
        )
    data = _skew_measure(n_workers, substrate=MeshSubstrate())
    data["n_devices"] = n_devices
    Path(out_path).parent.mkdir(parents=True, exist_ok=True)
    Path(out_path).write_text(json.dumps(data, indent=2))


def run_skew_sharded(n_devices: int = 8) -> list[tuple[str, float, str]]:
    """ISSUE 6 acceptance on the mesh: with a Zipf-skewed (exponent 1.4)
    workload on 8 devices, directory placement must deliver >= 1.5x the qps
    of hash placement and cut the max/mean shard-load ratio >= 2x.

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
         "from benchmarks.bench_balance import _skew_sharded_child; "
         f"_skew_sharded_child(n_devices={n_devices})"],
        check=True, cwd=str(root), env=env, timeout=900,
    )
    data = json.loads((root / _SKEW_ARTIFACT).read_text())
    assert data["n_rebalances"] >= 1, data
    assert data["speedup_x"] >= 1.5, data
    assert data["balance_x"] >= 2.0, data
    assert data["post_warm_recompiles"] == 0, data
    return _skew_rows(data, f"skew_sharded/w{data['n_workers']}"
                            f"d{data['n_devices']}")


if __name__ == "__main__":
    for r in run() + run_skew() + run_skew_sharded():
        print(",".join(map(str, r)))
