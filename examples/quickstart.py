"""Quickstart: the AdHash engine end to end in ~60 lines.

Loads a synthetic LUBM-like RDF graph, runs a query in distributed mode,
lets the engine adapt (heat map -> IRD -> pattern index), and shows the same
query answered in parallel mode with zero communication.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
from __future__ import annotations

import repro.core  # noqa: F401  (enables x64 for composite keys)
from repro.compile_cache import enable_compile_cache
from repro.core.engine import AdHashEngine
from repro.data.synthetic_rdf import Workload, lubm_like


def main() -> None:
    enable_compile_cache()
    # 1. generate + bulk-load data (subject-hash partitioning, ~1 second —
    #    the paper's "low startup" claim is the whole point)
    dictionary, triples = lubm_like(n_universities=4)
    engine = AdHashEngine(
        triples,
        n_workers=8,
        dictionary=dictionary,
        adaptive=True,
        frequency_threshold=3,
        replication_budget=5_000,
    )
    print(f"loaded {len(triples)} triples on {engine.w} workers "
          f"in {engine.startup_time_s:.2f}s")

    # 2. a cyclic query (students sharing their alma mater with the dept's
    #    university) — needs communication under plain hash partitioning
    workload = Workload(dictionary, mix={"q2": 1.0}, seed=0)
    for i in range(6):
        query = workload.sample(1)[0]
        rel, stats = engine.query(query)
        n = len(rel.to_numpy())
        print(
            f"query {i}: mode={stats.mode:17s} results={n:4d} "
            f"comm={stats.comm_bytes:8d}B plan={stats.plan[:2]}"
        )

    # 3. after the frequency threshold the pattern was redistributed:
    rep = engine.report
    print(
        f"\nredistributions={rep.n_redistributions} "
        f"replication_ratio={engine.replication_ratio():.3f} "
        f"parallel_queries={rep.n_parallel_replica}/{rep.n_queries}"
    )
    print("load balance:", engine.load_balance())

    # 4. decode a few result rows back to strings
    rel, _ = engine.query(workload.sample(1)[0])
    rows = rel.to_numpy()[:5]
    for row in rows:
        print("  ", [dictionary.decode_term(v) for v in row])


if __name__ == "__main__":
    main()
