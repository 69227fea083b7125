"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``: data sizes, engine settings) under a
traffic mix (``bench/traffic/<traffic>.json``: templates and weights, open
or closed loop, rate or clients, ``ServeConfig`` fields).  Per-layer metrics
are read by ``bench/metrics/<name>.py``.  A new cell, mix or metric is a new
file; nothing here names one.

One run, in one process (the process owns the chip):

  set-up   fail unless JAX's first device is a TPU and there are as many
           as the cell asks for; JAX's persistent compilation cache
           (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``);
           the graph from ``--seed``; ``AdHashEngine.ingest_stream``; the
           first request answered (``online_s`` ends there); warm-up until
           every template's route has settled and every batch class the
           window can form has run.  ``setup_s`` ends when the window opens.
  window   ``--seconds`` of traffic through ``ServeLoop`` on the wall clock;
           with ``--trace 1`` under the profiler, with host spans.
  check    every answered request's rows against ``bench/reference.py`` on
           the same triples, after the device's peak memory is read and
           the engine is freed.

Standard error carries the log; its last lines, and the result's last key
``checks``, give each number compared with its limit.  The control and the
planted faults that those limits were set against are read by
``bench/sweep.py`` and ``bench/tests``, never by these runs.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from bench import faults, lubm, reference, serve, traces  # noqa: E402

FAILED_MS = 1e9  # a percentile that falls on a failed request


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def cell_spec(workload: str, root: Path = ROOT) -> dict:
    """The cell's entry in ``<root>/BENCHMARK.json``, its configuration
    (the entry's ``file``) and traffic (``bench/traffic/<traffic>.json``),
    and the metric entries that apply to it."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; one of "
                         f"{sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "root": root,
        "cell": cell,
        "config": load_json(root / config["file"]),
        "traffic": load_json(
            root / "bench" / "traffic" / f"{cell['traffic']}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def reader(name: str, root: Path = ROOT):
    """``bench/metrics/<name>.py``'s ``read(record) -> float | None``; a
    metric split by cell (``device_idle.open``) with no file of its own
    is read by its quantity's (``device_idle.py``)."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        path = path.with_name(name.split(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Compiles:
    """Programs compiled or loaded from the cache (``jax.monitoring``)."""

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


class Requests:
    """Template instances as requests, numbered in the order made."""

    def __init__(self, graph, templates: dict, rng):
        self.graph, self.templates, self.rng = graph, templates, rng
        self.n = 0

    def make(self, name: str) -> serve.Req:
        from repro.core.query import Const, Query, TriplePattern, Var

        pats = lubm.instantiate(self.templates[name], self.graph, self.rng)
        term = {"var": Var, "const": Const}
        query = Query([TriplePattern(*(term[k](v) for k, v in p))
                       for p in pats], name=name)
        self.n += 1
        return serve.Req(self.n, name, pats,
                         lubm.template_vars(self.templates[name]), query)


class Session:
    """The graph, the engine and its serve loop, set up from one seed."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 spans: serve.Spans):
        import jax

        from repro.core.engine import AdHashEngine
        from repro.serving import ServeConfig

        self.traffic, self.spans = traffic, spans
        # the data set is the configuration's (LUBM(n, uba_seed)); the
        # run's seed draws the requests
        self.graph = lubm.generate(config["n_universities"],
                                   config["cardinalities"],
                                   config["uba_seed"])
        self.templates = lubm.load_templates(traffic["queries"])
        self.mix = traffic["mix"]
        self.requests = Requests(self.graph, self.templates,
                                 lubm.rng(seed, 2))
        log(f"graph: {len(self.graph.triples)} triples")
        chunk = int(config["ingest_chunk_rows"])
        tri = self.graph.triples
        t0 = serve.now()
        self.engine = AdHashEngine.ingest_stream(
            (tri[i:i + chunk] for i in range(0, len(tri), chunk)),
            **config["engine"])
        jax.block_until_ready(self.engine.store)
        self.ingest_s = serve.now() - t0
        self.cfg = ServeConfig(**traffic["serve"])
        # set-up's own front end: its service estimate has seen compiles,
        # so the window gets a loop of its own (``new_loop``)
        self.loop = self.new_loop()
        first = self.requests.make(next(iter(self.mix)))
        serve.serve_now(self.loop, [first], spans)
        self.online_s = serve.now() - t0
        if first.status != "answered":
            raise RuntimeError(f"first request {first.status}")
        log(f"backend: {self.engine.probe_backend}; ingest "
            f"{self.ingest_s:.3f} s; first answer at {self.online_s:.3f} s")

    def new_loop(self):
        from repro.runtime.fault_injection import WallClock
        from repro.serving import ServeLoop

        return ServeLoop(self.engine, self.cfg, clock=WallClock(),
                         service_model=None)

    def warm_up(self) -> None:
        """Until every template's route has settled, then every bucket
        size up to ``batch_target`` per template."""
        eng, names = self.engine, list(self.mix)
        t0 = serve.now()
        if eng.adaptive:
            need = 2 * eng.threshold
            hist: dict[str, list] = {t: [] for t in names}
            for i in range(4 * need * len(names)):
                t = names[i % len(names)]
                ird = eng.report.n_redistributions
                r = self.requests.make(t)
                serve.serve_now(self.loop, [r], self.spans)
                if r.status != "answered":
                    raise RuntimeError(f"warm-up request {r.status}")
                hist[t].append((r.mode, r.route,
                                eng.report.n_redistributions == ird))
                if all(len(h) >= need and len(set(h[-3:])) == 1
                       and h[-1][2] for h in hist.values()):
                    break
            else:
                raise RuntimeError(f"routes never settled: {hist}")
            log("settled routes: " + ", ".join(
                f"{t} {h[-1][0]}/{h[-1][1]}" for t, h in hist.items())
                + f"; {eng.report.n_redistributions} redistributions; "
                f"{serve.now() - t0:.3f} s")
        # every bucket size, not only every power-of-two class: padding a
        # bucket to its class compiles per size
        for b in range(1, self.cfg.batch_target + 1):
            for t in names:
                batch = [self.requests.make(t) for _ in range(b)]
                serve.serve_now(self.loop, batch, self.spans)
                if any(r.status != "answered" for r in batch):
                    raise RuntimeError(f"warm-up batch of {b} {t} failed")
        log(f"warm-up done: {serve.now() - t0:.3f} s")

    def window(self, seed: int, seconds: float, compiles: Compiles
               ) -> dict:
        """One window of the traffic; returns its requests and counts."""
        tr = self.traffic
        rng = lubm.rng(seed, 3)
        self.requests.rng = rng
        loop = self.new_loop()
        c0 = compiles.programs
        block = int(tr["block"])
        if tr["loop"] == "open":
            offs = lubm.arrival_offsets(float(tr["rate_qps"]), seconds)
            names = lubm.request_stream(self.mix, block, len(offs), rng)
            reqs = [self.requests.make(t) for t in names]
            t0 = serve.now()
            for r, off in zip(reqs, offs):
                r.due = t0 + float(off)
            with self.spans("window"):
                serve.open_loop(loop, reqs, self.spans)
        else:
            names = iter(lubm.request_stream(
                self.mix, block, 100_000, rng))
            t0 = serve.now()
            with self.spans("window"):
                reqs = serve.closed_loop(
                    loop, lambda: self.requests.make(next(names)),
                    int(tr["clients"]), t0 + seconds, self.spans)
        return {"requests": reqs, "t0": t0, "seconds": seconds,
                "compiles_in_window": compiles.programs - c0}


def percentile_ms(reqs, q: float) -> float:
    """Nearest-rank percentile of latency, failed requests infinite."""
    lat = sorted(r.latency for r in reqs)
    if not lat:
        return FAILED_MS
    v = lat[max(0, math.ceil(q / 100.0 * len(lat)) - 1)]
    return v * 1e3 if math.isfinite(v) else FAILED_MS


def truncated(want: np.ndarray) -> np.ndarray:
    """The control's answer: the exact one cut to the largest power of two
    below its size, as an undersized capacity class would cut it."""
    n = len(want)
    return want[:(1 << (n - 1).bit_length() - 1) if n > 1 else 0]


def check(triples, reqs, control: str | None = None,
          every_answered: bool = False) -> dict:
    """Each number compared, with its limit; rows against the reference.
    A request left pending or unexecutable is unanswered; where the
    traffic promises that every request is answered (``every_answered``),
    so is one shed or rejected."""
    index = reference.Index(triples)
    wrong = unanswered = checked = 0
    for r in reqs:
        if r.status in ("pending", "unexecutable") or (
                every_answered and r.status != "answered"):
            unanswered += 1
            continue
        if r.status != "answered":
            continue
        want = reference.evaluate(index, r.patterns, r.vars)
        got = (truncated(want) if control == "truncate"
               else reference.sorted_rows(r.rows))
        checked += 1
        if got.shape != want.shape or not np.array_equal(got, want):
            wrong += 1
            if wrong <= 5:
                log(f"wrong answer: request {r.rid} {r.template} "
                    f"({r.mode}/{r.route}): {len(got)} rows, "
                    f"{len(want)} in the reference")
    return {
        "checked": {"value": checked, "limit": 1},
        "wrong_answers": {"value": wrong, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
    }


def is_correct(checks: dict) -> bool:
    return (checks["checked"]["value"] >= checks["checked"]["limit"]
            and all(checks[k]["value"] <= checks[k]["limit"]
                    for k in ("wrong_answers", "unanswered")))


def prepare(spec: dict, seed: int) -> Session:
    """Set-up: the graph, the engine, the first answer and the warm-up."""
    compiles = Compiles()
    sess = Session(spec["config"], spec["traffic"], seed,
                   serve.Spans(on=False))
    sess.compiles = compiles
    sess.warm_up()
    log(f"set-up: {sess.compiles.programs} programs compiled or loaded, "
        f"{sess.compiles.cache_hits} from the persistent cache")
    return sess


def measure(sess: Session, seed: int, seconds: float, trace: bool,
            fault: str | None = None) -> dict:
    """One window, traced or not, with ``fault`` planted for its length."""
    import jax

    restore = faults.plant(sess.engine, fault) if fault else None
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        sess.spans.on = True
    try:
        w = sess.window(seed, seconds, sess.compiles)
    finally:
        if trace:
            jax.profiler.stop_trace()
            sess.spans.on = False
        if restore:
            restore()
    reqs = w["requests"]
    lag = sorted(r.offered - r.due for r in reqs if r.offered is not None)
    status: dict[str, int] = {}
    for r in reqs:
        status[r.status] = status.get(r.status, 0) + 1
    log(f"window: {len(reqs)} requests {status}; generator lag p95 "
        f"{1e3 * lag[int(0.95 * (len(lag) - 1))] if lag else 0:.3f} ms; "
        f"{w['compiles_in_window']} programs compiled in the window")
    w["trace"] = None
    if trace_dir:
        w["trace"] = traces.reduce_file(traces.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    return w


def evaluate(spec: dict, sess: Session, w: dict, triples,
             control: str | None = None) -> dict:
    """The window's metrics and the check of its answers."""
    reqs = w["requests"]
    t_check = serve.now()
    checks = check(triples, reqs, control,
                   spec["traffic"].get("every_request_answered", False))
    log(f"check: {serve.now() - t_check:.3f} s")
    done = [r.finished for r in reqs if r.status == "answered"]
    e2e = {
        "setup_s": w["t0"] - T_START,
        "online_s": sess.online_s,
        "p50_ms": percentile_ms(reqs, 50),
        "p95_ms": percentile_ms(reqs, 95),
        # every request sent in the window, over the time from the window's
        # start until the last of them was answered: a closed loop answers
        # its clients in batches, and a count cut at the close would move
        # in steps of a whole batch
        "qps": len(done) / (max(done) - w["t0"]) if done else 0.0,
    }
    record = {
        "requests": reqs,
        "ingest_s": sess.ingest_s,
        "compiles_in_window": w["compiles_in_window"],
        "trace": w["trace"],
    }
    return {"e2e": e2e, "record": record, "checks": checks,
            "failed": sum(1 for r in reqs if r.status != "answered"),
            "attempted": len(reqs)}


def run_cell(spec: dict, seed: int, seconds: float, trace: bool) -> dict:
    """Set-up, window and check of one cell: the result's numbers and the
    run's record for the metric readers."""
    import jax

    from repro.core import backend as data_plane

    sess = prepare(spec, seed)
    w = measure(sess, seed, seconds, trace)
    log(f"implementations per primitive: {data_plane.resolved_impls()}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:spec["cell"]["chips"]])
    triples, backend = sess.graph.triples, sess.engine.probe_backend
    # the program's state goes before the reference runs
    sess.engine = sess.loop = None
    gc.collect()
    run = evaluate(spec, sess, w, triples)
    run.update(memory_peak_bytes=int(peak), backend=backend)
    return run


def metrics_of(spec: dict, run: dict, trace: bool) -> dict:
    if not trace:
        return {m["name"]: {"value": run["e2e"][m["name"]], "unit": m["unit"]}
                for m in spec["end_to_end"]}
    out = {}
    for m in spec["per_layer"]:
        v = reader(m["name"], spec["root"])(run["record"])
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = cell_spec(args.workload)

    import jax

    devices = jax.devices()
    chips = int(spec["cell"]["chips"])
    if devices[0].platform != "tpu":
        log(f"bench: no TPU found (JAX sees {devices[0].platform}); "
            "the benchmark runs only on a TPU")
        return 2
    if len(devices) < chips:
        log(f"bench: {len(devices)} TPU device(s), the cell needs {chips}")
        return 2
    kind = devices[0].device_kind
    peaks = load_json(BENCH / "peaks.json")
    if kind not in peaks:
        log(f"bench: device kind {kind!r} is not in bench/peaks.json")
        return 2
    log(f"device: {kind} x {len(devices)}; peaks {peaks[kind]}")
    from repro.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    backend = spec["config"].get("expect_backend")

    run = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    checks = run["checks"]
    result = {
        "correct": is_correct(checks),
        "attempted": run["attempted"],
        "failed": run["failed"],
        "metrics": metrics_of(spec, run, bool(args.trace)),
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices),
                   "memory_peak_bytes": run["memory_peak_bytes"]},
    }
    if backend and run["backend"] != backend:
        log(f"bench: backend resolved to {run['backend']}, not {backend}")
        result["correct"] = False
    summary = run["record"]["trace"]
    if summary is not None:
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
        log(f"idle by host span: {summary['idle_by_span']}")
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
