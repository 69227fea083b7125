"""Several windows of one cell after one set-up, for the measurements that
fix the benchmark's numbers: a rate sweep to find the knee, and readings
of the check over many seeds with the control beside each.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        [--rates 10,20,40] [--seeds 1,2,3] [--faults alter,half_batch]

Set-up runs once from ``--seed``.  Then one window per rate (the traffic's
``rate_qps`` replaced), or one per seed of ``--seeds`` (that seed's
traffic), or one per planted fault.  Each window prints one JSON line: its
end-to-end numbers, its per-layer numbers, the check of its answers, the
control's check of the same requests, and per route the count and median
latency.  The benchmark's own runs are ``run.py``'s; this tool is not one.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys

import run
from run import is_correct, log, measure, metrics_of, prepare


def _ints(s: str) -> list[int]:
    return [int(x) for x in s.split(",") if x]


def _floats(s: str) -> list[float]:
    return [float(x) for x in s.split(",") if x]


def window_line(spec, sess, seed, seconds, fault=None, **extra) -> dict:
    w = measure(sess, seed, seconds, trace=False, fault=fault)
    out = run.evaluate(spec, sess, w, sess.graph.triples)
    control = run.evaluate(spec, sess, w, sess.graph.triples, "truncate")
    routes: dict[str, list] = {}
    for r in w["requests"]:
        routes.setdefault(f"{r.template} {r.mode}/{r.route} {r.status}",
                          []).append(r.latency * 1e3)
    return {
        **extra, "seed": seed, "fault": fault,
        "attempted": out["attempted"], "failed": out["failed"],
        "e2e": out["e2e"],
        "per_layer": {k: v["value"]
                      for k, v in metrics_of(spec, out, True).items()},
        "correct": is_correct(out["checks"]), "checks": out["checks"],
        "control_correct": is_correct(control["checks"]),
        "control_checks": control["checks"],
        "routes": {k: [len(v), statistics.median(v)]
                   for k, v in sorted(routes.items())},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=_floats, default=[])
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    spec = run.cell_spec(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        log("sweep: no TPU found")
        return 2
    from repro.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    sess = prepare(spec, args.seed)
    log(f"set-up done at {run.serve.now() - run.T_START:.3f} s")
    seed = args.seed
    for rate in args.rates:
        sess.traffic = {**sess.traffic, "rate_qps": rate}
        seed += 1
        print(json.dumps(window_line(spec, sess, seed, args.seconds,
                                     rate=rate)), flush=True)
    for s in args.seeds:
        print(json.dumps(window_line(spec, sess, s, args.seconds)),
              flush=True)
    for f in [f for f in args.faults.split(",") if f]:
        seed += 1
        print(json.dumps(window_line(spec, sess, seed, args.seconds,
                                     fault=f)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
