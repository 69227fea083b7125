"""Windows of one cell with the program's own spans and counters read: the
per-layer numbers that ``run.py`` does not report yet.

    python3 bench/program_trace.py --workload <cell> --seed <n> \\
        --seconds <s> --seeds 1,2,3

Set-up runs once from ``--seed`` (``run.prepare``).  Then, for each seed of
``--seeds``, one window under the profiler with the program's spans on
(``repro.core.trace.enable``) and one window without either, on the same
seed's traffic, the traced one first for every other seed; each prints one
JSON line: its end-to-end numbers, the check of its answers and these
numbers, read from the program:

  queue_wait_ms         nearest-rank p95, over answered requests, of
                        ``ServedResult.dispatched_s`` - arrival
  requests_per_dispatch requests over bucket dispatches
                        (``ServeReport.bucket_sizes``, singletons included)
  host_syncs_per_query  ``EngineReport.host_syncs`` over the window, per
                        answered request
  host_self_ms_per_query (traced) summed self time of the ``adhash.*`` spans
                        that do not wait on the device, per answered request
  idle_in_program       (traced) share (%) of the window in which the device
                        runs no operation while an ``adhash.*`` span is open

A traced line also gives self time and program idle by span name, the
syncs by chokepoint, and device time by ``jax.named_scope`` where the
device's operations carry their scope.  The benchmark's own runs are
``run.py``'s; this tool is not one.
"""
from __future__ import annotations

import argparse
import bisect
import json
import math
import shutil
import sys
import tempfile
from collections import Counter, defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run, traces  # noqa: E402

PREFIX = "adhash."
# spans in which the host waits on the device: not host self time
WAITS = ("adhash.sync", "adhash.ird_wait")
# jax.named_scope names of the data plane (core/dsj.py)
SCOPES = ("probe", "expand", "bucket_by_dest", "unique_compact",
          "exchange_hash", "probe_and_reply", "local_chain", "finalize_join")


# ------------------------------------------------------------ the trace
def program_events(profile) -> tuple[list, list]:
    """``adhash.*`` spans as ``(name, thread, start_ns, end_ns, stats)``
    (thread: the host line they ran on), and the device operations with
    their stats, ``(device, start, end, stats)``."""
    spans, ops = [], []
    for plane in profile.planes:
        on_device = plane.name.startswith("/device:")
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                if on_device and line.name == "XLA Ops":
                    ops.append((plane.name, s, e, traces._stats(ev)))
                elif not on_device and ev.name.startswith(PREFIX):
                    spans.append((ev.name, (plane.name, i), s, e,
                                  traces._stats(ev)))
    return spans, ops


def _intersect(a, b) -> float:
    """Length of the overlap of two merged, sorted interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def self_times(spans) -> list[tuple[str, float]]:
    """``(name, self ns)`` of each span of one thread: its duration less
    the part its direct children on the thread cover."""
    out: list[tuple[str, float]] = []
    stack: list[list] = []  # [name, start, end, covered by children]

    def close():
        name, s, e, cover = stack.pop()
        out.append((name, e - s - cover))

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close()
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    while stack:
        close()
    return out


def reduce_program(device: dict, window: tuple[float, float],
                   spans: list) -> dict:
    """Host self time and device idle under ``adhash.*`` spans, in the
    window.  ``device`` is ``traces.events``' first output; ``spans`` is
    ``program_events``' first."""
    lo, hi = window
    spans = [sp for sp in spans if lo <= sp[2] < hi]
    by_thread = defaultdict(list)
    for name, thread, s, e, _ in spans:
        by_thread[thread].append((name, s, min(e, hi)))
    self_by_name: dict[str, float] = defaultdict(float)
    for thread_spans in by_thread.values():
        for name, ns in self_times(thread_spans):
            self_by_name[name[len(PREFIX):]] += ns / 1e9
    host_self = sum(v for k, v in self_by_name.items()
                    if PREFIX + k not in WAITS)
    program = traces.union(traces.clip([(s, e) for _, _, s, e, _ in spans],
                                       lo, hi))
    idle_in = []
    idle_by_name: dict[str, float] = defaultdict(float)
    for evs in device.values():
        busy = traces.union(traces.clip([(s, e) for _, s, e in evs], lo, hi))
        idle = traces.gaps(busy, lo, hi)
        idle_in.append(_intersect(idle, program))
        starts = [s for s, _ in idle]
        for name, _, s, e, _ in spans:
            # the idle stretches that can overlap [s, e)
            k = max(0, bisect.bisect_right(starts, s) - 1)
            near = idle[k:bisect.bisect_left(starts, e)]
            idle_by_name[name[len(PREFIX):]] += _intersect(
                near, [(s, min(e, hi))]) / len(device) / 1e9
    return {
        "host_self_s": host_self,
        "self_by_span": dict(self_by_name),
        "idle_in_program_share": sum(idle_in) / len(idle_in) / (hi - lo),
        # an idle stretch inside nested spans counts once for each
        "idle_by_span": dict(idle_by_name),
        "syncs_by_what": dict(Counter(str(sp[4].get("what")) for sp in spans
                                      if sp[0] == PREFIX + "sync")),
    }


def device_by_scope(ops: list, window: tuple[float, float]) -> dict:
    """Device seconds per data-plane scope: the union of the intervals of
    the operations whose stats name the scope in a path (``.../probe/...``;
    the innermost such name), averaged over the devices; with the stat keys
    the operations carry and the stats of the longest one."""
    keys: set = set()
    devices = {d for d, *_ in ops}
    by_scope: dict[str, list] = defaultdict(list)
    longest = max(ops, key=lambda op: op[2] - op[1], default=None)
    for _, s, e, stats in ops:
        keys.update(stats)
        scope = "unscoped"
        for v in stats.values():
            found = [p for p in str(v).split("/") if p in SCOPES]
            if found:
                scope = found[-1]
                break
        by_scope[scope].append((s, e))
    return {"stat_keys": sorted(keys),
            "longest_op_stats": {k: str(v)[:200] for k, v in
                                 (longest[3] if longest else {}).items()},
            "by_scope": {k: sum(e - s for s, e in traces.union(
                traces.clip(v, *window))) / 1e9 / len(devices)
                for k, v in by_scope.items()}}


# ---------------------------------------------------------- the numbers
def per_layer(w: dict) -> dict:
    """The program's per-layer numbers of one window (``window``'s
    output)."""
    answered = [r for r in w["requests"] if r.status == "answered"]
    res = w["results"]
    waits = sorted(res[r.rid].dispatched_s - r.due for r in answered)
    sizes = w["bucket_sizes"]
    out = {
        "queue_wait_ms": (1e3 * waits[max(0, math.ceil(0.95 * len(waits))
                                          - 1)] if waits else None),
        "requests_per_dispatch": (
            sum(n * k for n, k in sizes.items()) / sum(sizes.values())
            if sizes else None),
        "host_syncs_per_query": (w["host_syncs"] / len(answered)
                                 if answered else None),
    }
    prog = w.get("program")
    if prog is not None and answered:
        out["host_self_ms_per_query"] = 1e3 * prog["host_self_s"] / len(
            answered)
        out["idle_in_program"] = 100.0 * prog["idle_in_program_share"]
    return out


# ---------------------------------------------------------- the windows
def window(sess, seed: int, seconds: float, traced: bool) -> dict:
    """One window of the cell, its results kept by rid; with ``traced``
    under the profiler with the program's and the benchmark's spans."""
    import jax
    from jax.profiler import ProfileData

    from repro.core import trace

    loop = sess.new_loop()
    results: dict = {}
    take = loop.take_completions

    def keep():
        out = take()
        results.update((r.rid, r) for r in out)
        return out

    loop.take_completions = keep
    new_loop, sess.new_loop = sess.new_loop, lambda: loop
    syncs0 = sess.engine.report.host_syncs
    trace_dir = tempfile.mkdtemp(prefix="program-trace-") if traced else None
    try:
        if traced:
            jax.profiler.start_trace(trace_dir)
            sess.spans.on = True
            trace.enable(True)
        w = sess.window(seed, seconds, sess.compiles)
    finally:
        sess.new_loop = new_loop
        if traced:
            trace.enable(False)
            sess.spans.on = False
            jax.profiler.stop_trace()
    w.update(results=results, bucket_sizes=dict(loop.report.bucket_sizes),
             host_syncs=sess.engine.report.host_syncs - syncs0,
             trace=None, program=None)
    if traced:
        profile = ProfileData.from_file(str(traces.find_xplane(trace_dir)))
        device, spans, programs = traces.events(profile)
        w["trace"] = traces.reduce(device, spans, programs)
        win = next((s, e) for n, s, e in spans
                   if n == traces.SPAN_PREFIX + "window")
        prog_spans, ops = program_events(profile)
        w["spans"] = (spans, prog_spans)
        w["program"] = reduce_program(device, win, prog_spans)
        w["program"]["device_by_scope"] = device_by_scope(ops, win)
        shutil.rmtree(trace_dir, ignore_errors=True)
    return w


def window_line(spec, sess, seed: int, seconds: float, traced: bool
                ) -> dict:
    w = window(sess, seed, seconds, traced)
    out = run.evaluate(spec, sess, w, sess.graph.triples)
    line = {"seed": seed, "traced": traced, "attempted": out["attempted"],
            "failed": out["failed"], "e2e": out["e2e"],
            "correct": run.is_correct(out["checks"]),
            "per_layer": per_layer(w)}
    if traced:
        tr, prog = w["trace"], w["program"]
        line.update(device_idle=100.0 * tr["idle_share"],
                    idle_by_bench_span=tr["idle_by_span"],
                    device_ops=tr["device_ops"], **prog)
        run.log(f"idle by host span: {tr['idle_by_span']}")
        run.log(f"idle by program span: {prog['idle_by_span']}")
        run.log(f"device time by scope: {prog['device_by_scope']}")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated; a traced and an untraced window "
                         "each")
    args = ap.parse_args(argv)
    spec = run.cell_spec(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        run.log("program_trace: no TPU found")
        return 2
    from repro.compile_cache import enable_compile_cache

    run.log(f"compile cache: {enable_compile_cache()}")
    sess = run.prepare(spec, args.seed)
    run.log(f"set-up done at {run.serve.now() - run.T_START:.3f} s")
    for i, s in enumerate(int(x) for x in args.seeds.split(",") if x):
        # the second window of a seed repeats the first one's requests and
        # finds the host memos (plans, stage constants) warm: alternate
        # which of the two goes first
        for traced in ((True, False) if i % 2 == 0 else (False, True)):
            print(json.dumps(window_line(spec, sess, s, args.seconds,
                                         traced)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
