"""Workload adaptivity demo (paper Figs 13/14): run a shifting query
workload with and without adaptivity and print the cumulative-cost curves.

Run:  PYTHONPATH=src python examples/rdf_workload.py
"""
from __future__ import annotations

import time

import repro.core  # noqa: F401
from repro.compile_cache import enable_compile_cache
from repro.core.engine import AdHashEngine
from repro.data.synthetic_rdf import Workload, lubm_like


def run_engine(adaptive: bool, triples, d, order, per_phase=20):
    eng = AdHashEngine(triples, 8, adaptive=adaptive, frequency_threshold=4)
    wl = Workload(d, seed=3)
    cum_t, cum_c = [], []
    t_total = c_total = 0.0
    for name in order:
        for _ in range(per_phase):
            q = wl.templates[name].instantiate(wl.rng)
            t0 = time.perf_counter()
            _, st = eng.query(q)
            t_total += time.perf_counter() - t0
            c_total = (eng.report.comm_cells + eng.report.ird_comm_cells) * 4
            cum_t.append(t_total)
            cum_c.append(c_total)
    return eng, cum_t, cum_c


def sparkline(values, width=48):
    blocks = " .:-=+*#%@"
    mx = max(values) or 1
    idx = [int((len(blocks) - 1) * v / mx) for v in values]
    step = max(1, len(idx) // width)
    return "".join(blocks[i] for i in idx[::step])


def main() -> None:
    enable_compile_cache()
    d, triples = lubm_like(n_universities=4)
    order = ["q1", "q12", "q7", "q2"]  # workload shifts every 20 queries

    na, t_na, c_na = run_engine(False, triples, d, order)
    ad, t_ad, c_ad = run_engine(True, triples, d, order)

    print("cumulative wall time (each char = 2 queries; phases shift q1->q12->q7->q2)")
    print(f"  AdHash-NA {t_na[-1]:7.2f}s |{sparkline(t_na)}|")
    print(f"  AdHash    {t_ad[-1]:7.2f}s |{sparkline(t_ad)}|")
    print("cumulative communication bytes")
    print(f"  AdHash-NA {c_na[-1]:9.0f}B |{sparkline(c_na)}|")
    print(f"  AdHash    {c_ad[-1]:9.0f}B |{sparkline(c_ad)}|")
    print(
        f"\nAdHash answered "
        f"{ad.report.n_parallel_replica + ad.report.n_parallel}"
        f"/{ad.report.n_queries} queries in parallel mode, "
        f"{ad.report.n_redistributions} IRD redistributions, "
        f"replication {ad.replication_ratio():.2f}, "
        f"{ad.report.n_evictions} evictions"
    )
    speedup = t_na[-1] / max(t_ad[-1], 1e-9)
    comm_ratio = c_na[-1] / max(c_ad[-1], 1)
    print(f"speedup {speedup:.1f}x, communication reduced {comm_ratio:.1f}x")


if __name__ == "__main__":
    main()
