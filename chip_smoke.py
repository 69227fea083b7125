"""Drive the AdHash engine's main path once on a TPU, at LUBM-100 scale.

Phases, in one process (one process owns the chip):

  device     fail unless JAX's first device is a TPU (there is no CPU path)
  cache      JAX's persistent compilation cache (``repro.compile_cache``)
  data       a LUBM-like graph from ``--seed`` — 150 universities x 15
             departments x 10 professors x 100 students x 2 courses is
             13,662,000 triples, the size of LUBM-100 (about 13 M triples
             in the published UBA generator)
  load       ``AdHashEngine.ingest_stream`` over 8 workers with the default
             data-plane backend, which must resolve to ``pallas``
  queries    every ``lubm_queries`` template through ``engine.query``; both
             the fused case-(i) chain and the distributed DSJ route run
  adaptivity one non-star template repeated past the frequency threshold
             until IRD fires and the next instance is a pattern-index hit
             with no communication
  batch      one ``query_batch`` of 16 queries (q1 chains stacked in one
             dispatch, q12 pattern-index hits) against sequential answers
  serving    eight requests through ``ServeLoop`` on the wall clock

Every answer is compared exactly with ``tests/reference.py``'s numpy
evaluator on the same triples.  Any mismatch or exception exits nonzero.
The last line of standard output is one JSON object naming the device.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # only the worker axis sharded over
                                      # four chips, and its comparison
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
FULL = {
    "n_universities": 150,
    "depts_per_univ": 15,
    "profs_per_dept": 10,
    "students_per_prof": 100,
    "courses_per_prof": 2,
}
N_WORKERS = 8
CHUNK = 1 << 20  # ingest chunk rows


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _import_paths() -> None:
    for p in (ROOT / "src", ROOT / "tests"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


class _Compiles:
    """Programs compiled or loaded, and persistent-cache hits among them."""

    def __init__(self):
        import jax

        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class _Phases:
    """Seconds and programs compiled per phase, printed as each ends."""

    def __init__(self, log, compiles: _Compiles):
        self.log = log
        self.compiles = compiles
        self.seconds: dict[str, float] = {}
        self.programs: dict[str, int] = {}

    def run(self, name: str, fn, *args, **kwargs):
        p0 = self.compiles.programs
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        self.seconds[name] = time.perf_counter() - t0
        self.programs[name] = self.compiles.programs - p0
        self.log(f"[{name}] {self.seconds[name]:.2f} s, "
                 f"{self.programs[name]} programs")
        return out


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    return out, time.perf_counter() - t0


def _answers(rel, q) -> "np.ndarray":
    import numpy as np
    from reference import unique_rows

    return unique_rows(rel.project_to(q.vars).astype(np.int64))


def _same(rel, q, triples, what: str) -> int:
    """Compare one engine answer with the numpy reference; returns rows."""
    import numpy as np
    from reference import evaluate_bgp

    want = evaluate_bgp(triples, q)
    got = _answers(rel, q)
    _check(got.shape == want.shape and np.array_equal(got, want),
           f"{what}: {len(got)} rows vs {len(want)} in the reference")
    return len(want)


def _load(triples, dictionary, backend, substrate, **engine_kw):
    from repro.core.engine import AdHashEngine

    chunks = (triples[i:i + CHUNK] for i in range(0, len(triples), CHUNK))
    engine = AdHashEngine.ingest_stream(
        chunks, N_WORKERS, dictionary=dictionary, probe_backend=backend,
        substrate=substrate, **engine_kw,
    )
    import jax

    jax.block_until_ready(engine.store)
    return engine


def _instances(templates, names, n, rng):
    out = []
    while len(out) < n:
        for name in names:
            if len(out) < n:
                out.append(templates[name].instantiate(rng))
    return out


def run_smoke(sizes: dict, *, seed: int = 0, backend: str = "auto",
              threshold: int = 16, log=print) -> dict:
    """Every phase after the device check, at ``sizes`` (``lubm_like``
    arguments).  Raises on any mismatch; returns the run's record.

    Only q12 is repeated past ``threshold``: q1 runs 13 times in all and
    stays on the main-index chain, so the batch stacks it."""
    _import_paths()
    import jax
    import numpy as np

    from repro.core import backend as data_plane
    from repro.data.synthetic_rdf import lubm_like, lubm_queries
    from repro.runtime.fault_injection import WallClock
    from repro.serving import Request, ServeConfig, ServedResult, ServeLoop

    compiles = _Compiles()
    ph = _Phases(log, compiles)
    rng = np.random.default_rng(seed)

    d, triples = ph.run("data", lubm_like, **sizes, seed=seed)
    log(f"triples: {len(triples)}")
    engine = ph.run("load", _load, triples, d, backend, None,
                    frequency_threshold=threshold)
    leaves = jax.tree.leaves(engine.store)
    store_bytes = sum(x.nbytes for x in leaves)
    (device,) = set().union(*(x.sharding.device_set for x in leaves))
    log(f"backend: {engine.probe_backend}; store on device {device.id}: "
        f"{store_bytes} bytes")
    templates = lubm_queries(d)

    # -- every template once: case-(i) chain and DSJ routes both run
    def queries():
        routes = {}
        for name, tpl in templates.items():
            q = tpl.instantiate(rng)
            (rel, st), t_q = _timed(engine.query, q)
            rows, t_c = _timed(_same, rel, q, triples, f"query {name}")
            routes[name] = (st.mode, st.route, st.n_dsj)
            log(f"  {name}: {rows} rows, mode={st.mode} "
                f"route={st.route or 'dsj'} dsj={st.n_dsj} "
                f"comm_cells={st.comm_cells}; query {t_q:.2f} s, "
                f"check {t_c:.2f} s")
        _check(any(r.endswith("-local-main") for _, r, _ in routes.values()),
               "no query took the fused case-(i) chain")
        _check(any(n > 0 for *_, n in routes.values()),
               "no query took the distributed DSJ route")
        return routes

    routes = ph.run("queries", queries)

    # -- adaptivity: a hot non-star template until IRD makes it a PI hit
    def adaptivity():
        hot = templates["q12"]
        for i in range(2 * threshold + 2):
            q = hot.instantiate(rng)
            (rel, st), t_q = _timed(engine.query, q)
            _same(rel, q, triples, f"adaptivity instance {i}")
            log(f"  instance {i}: mode={st.mode}; query {t_q:.2f} s")
            if st.mode == "parallel-replica":
                _check(st.comm_cells == 0, "pattern-index hit communicated")
                log(f"  pattern-index hit after {i + 1} instances, "
                    f"{engine.report.n_redistributions} redistribution(s)")
                return i + 1
        raise SmokeFailure("IRD never turned q12 into a pattern-index hit")

    ph.run("adaptivity", adaptivity)

    # -- one batch against the same queries run one by one: case-(i) chains
    # stacked into one dispatch, and the now-hot q12 as pattern-index hits
    batch = _instances(templates, ("q1", "q12", "q12", "q12"), 16, rng)

    def batch_phase():
        out, t_b = _timed(engine.query_batch, batch)
        t_s = t_c = 0.0
        for i, (q, (rel, _st)) in enumerate(zip(batch, out)):
            (seq, _), dt = _timed(engine.query, q)
            t_s += dt
            t0 = time.perf_counter()
            _same(rel, q, triples, f"batch member {i}")
            _check(np.array_equal(_answers(rel, q), _answers(seq, q)),
                   f"batch member {i} differs from its sequential answer")
            t_c += time.perf_counter() - t0
        log(f"  query_batch {t_b:.2f} s, the same {len(out)} one by one "
            f"{t_s:.2f} s, checks {t_c:.2f} s")
        return len(out)

    ph.run("batch", batch_phase)

    # -- serving on the wall clock, no service model; the same shapes as
    # the batch (batch_target = the batch's q1 bucket), so no new programs
    reqs = _instances(templates, ("q1", "q12"), 8, rng)

    def serving():
        cfg = ServeConfig(slo_s=600.0, batch_target=4)
        loop = ServeLoop(engine, cfg, clock=WallClock(), service_model=None)
        t0 = time.perf_counter()
        for rid, q in enumerate(reqs):
            _check(loop.offer(Request(rid, q)) is None,
                   f"request {rid} rejected")
        results = loop.pump() + loop.drain()
        t_l = time.perf_counter() - t0
        served = {r.rid: r for r in results if isinstance(r, ServedResult)}
        _check(sorted(served) == list(range(len(reqs))),
               f"served {sorted(served)} of {len(reqs)} requests")
        t0 = time.perf_counter()
        for rid, q in enumerate(reqs):
            _same(served[rid].relation, q, triples, f"request {rid}")
        log(f"  served {len(served)} in {t_l:.2f} s, checks "
            f"{time.perf_counter() - t0:.2f} s")
        return len(served)

    ph.run("serving", serving)

    stats = device.memory_stats() or {}
    record = {
        "triples": int(len(triples)),
        "backend": engine.probe_backend,
        "devices": 1,
        "seconds": ph.seconds,
        "phase_programs": ph.programs,
        "store_bytes": int(store_bytes),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "programs": compiles.programs,
        "cache_hits": compiles.cache_hits,
        "impls": data_plane.resolved_impls(),
        "routes": routes,
    }
    log(f"peak_bytes_in_use: {record['peak_bytes_in_use']}")
    log(f"programs compiled or loaded: {compiles.programs} "
        f"(persistent-cache hits {compiles.cache_hits})")
    log(f"implementation per primitive (traced call sites): "
        f"{record['impls']}")
    return record


def run_sharded(sizes: dict, n_chips: int, *, seed: int = 0,
                backend: str = "auto", log=print) -> dict:
    """The worker axis sharded over ``n_chips`` devices (DSJ exchanges as
    all_to_all / all_gather): every template plus one batch, compared with
    the reference, and the store checked to span every chip."""
    _import_paths()
    import jax
    import numpy as np

    from repro.core import backend as data_plane
    from repro.core.substrate import MeshSubstrate
    from repro.data.synthetic_rdf import lubm_like, lubm_queries

    compiles = _Compiles()
    ph = _Phases(log, compiles)
    rng = np.random.default_rng(seed)
    devices = jax.devices()[:n_chips]
    _check(len(devices) == n_chips, f"{len(jax.devices())} devices, "
           f"{n_chips} wanted")

    d, triples = ph.run("data", lubm_like, **sizes, seed=seed)
    log(f"triples: {len(triples)}")
    # adaptivity off: this path checks the sharded exchanges, not IRD
    engine = ph.run("load", _load, triples, d, backend,
                    MeshSubstrate(devices=devices), adaptive=False)
    for leaf in jax.tree.leaves(engine.store):
        _check(len(leaf.sharding.device_set) == n_chips,
               f"store array on {len(leaf.sharding.device_set)} devices")
    log(f"backend: {engine.probe_backend}; store spans {n_chips} devices")
    templates = lubm_queries(d)

    def queries():
        for name, tpl in templates.items():
            q = tpl.instantiate(rng)
            (rel, st), t_q = _timed(engine.query, q)
            rows, t_c = _timed(_same, rel, q, triples, f"query {name}")
            log(f"  {name}: {rows} rows, mode={st.mode} "
                f"route={st.route or 'dsj'} comm_cells={st.comm_cells}; "
                f"query {t_q:.2f} s, check {t_c:.2f} s")

    ph.run("queries", queries)
    # chains stacked on the batch axis, and batched all_to_all exchanges
    batch = _instances(templates, ("q1", "q7"), 16, rng)

    def batch_phase():
        for i, (q, (rel, _)) in enumerate(zip(batch,
                                              engine.query_batch(batch))):
            _same(rel, q, triples, f"batch member {i}")

    ph.run("batch", batch_phase)
    record = {
        "triples": int(len(triples)),
        "backend": engine.probe_backend,
        "devices": n_chips,
        "seconds": ph.seconds,
        "peak_bytes_in_use": [
            (dv.memory_stats() or {}).get("peak_bytes_in_use")
            for dv in devices
        ],
        "programs": compiles.programs,
        "impls": data_plane.resolved_impls(),
    }
    log(f"peak_bytes_in_use per chip: {record['peak_bytes_in_use']}")
    log(f"programs compiled or loaded: {compiles.programs}")
    log(f"implementation per primitive (traced call sites): "
        f"{record['impls']}")
    return record


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform} "
              "devices); this check runs only on a TPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {len(devices)} TPU device(s), --chips "
              f"{args.chips} wanted", file=sys.stderr)
        return 2
    _import_paths()
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {devices[0].device_kind} x {len(devices)}")
    if args.chips == 1:
        record = run_smoke(FULL, seed=args.seed)
    else:
        record = run_sharded(FULL, args.chips, seed=args.seed)
    _check(record["backend"] == "pallas",
           f"backend resolved to {record['backend']}, not pallas")
    print(json.dumps({"record": record}, default=str))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": record["devices"],
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
