"""Reduce a profiler trace to the numbers the benchmark reports.

``jax.profiler`` writes an ``.xplane.pb``; ``ProfileData`` reads it.  Device
operations are the events on the ``XLA Ops`` line of each ``/device:`` plane
(on a host without an accelerator, where XLA runs on the CPU, the events on
host threads that carry an ``hlo_op`` stat).  Host spans are the
benchmark's own ``TraceAnnotation``s, named ``bench.<what>``.

Busy time is the union of the device-op intervals inside the window (the
``bench.window`` span); idle share is one minus busy over the window.  Each
idle gap is labelled by the host span that covers most of it.  Device time
per program comes from the ``XLA Modules`` line, whose events do not nest
as the operations inside a loop do.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from pathlib import Path

SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str | Path) -> Path:
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(found[-1])


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def events(profile) -> tuple[dict, list, dict]:
    """(device ops per device, host spans, programs per device) from a
    ``ProfileData``: ``{device: [(name, start_ns, end_ns)]}``,
    ``[(name, start, end)]`` and ``{device: [(program, start, end)]}``
    (the ``XLA Modules`` line; none on a host without an accelerator)."""
    device: dict[str, list] = defaultdict(list)
    programs: dict[str, list] = defaultdict(list)
    host_ops: list = []
    spans: list = []
    for plane in profile.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.name, float(ev.start_ns),
                      float(ev.start_ns + ev.duration_ns))
                if on_device:
                    if line.name == "XLA Ops":
                        device[plane.name].append(iv)
                    elif line.name == "XLA Modules":
                        programs[plane.name].append(iv)
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append(iv)
                elif "hlo_op" in _stats(ev):
                    host_ops.append(iv)
    if not device and host_ops:
        device["/host:CPU"] = host_ops
    return dict(device), spans, dict(programs)


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of merged ``busy`` inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _label(gap, spans) -> str:
    """The innermost-named host span covering most of ``gap``."""
    best, best_cover = "untraced", 0.0
    for name, s, e in spans:
        if name == SPAN_PREFIX + "window":
            continue
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best_cover:
            best, best_cover = name[len(SPAN_PREFIX):], cover
    return best


def _short(name: str) -> str:
    """``jit_probe(1234)`` -> ``jit_probe``; ``%fusion.9 = u32[...] ...`` ->
    ``%fusion.9``."""
    return re.sub(r"\(\d+\)$", "", name.split(" = ")[0])


def reduce(device: dict, spans: list, programs: dict | None = None,
           top: int = 10) -> dict:
    """busy_s, window_s, idle_share and the breakdown lists.  Device time
    in the breakdown is per program where the trace has programs, else per
    operation, each averaged over the devices."""
    windows = [(s, e) for n, s, e in spans if n == SPAN_PREFIX + "window"]
    if not windows or not device:
        raise ValueError("trace holds no window span or no device ops")
    lo, hi = windows[0]
    busy_per, gap_list = [], []
    for evs in device.values():
        busy = union(clip([(s, e) for _, s, e in evs], lo, hi))
        busy_per.append(sum(e - s for s, e in busy))
        gap_list.extend(gaps(busy, lo, hi))
    per_name = defaultdict(float)
    for evs in (programs or device).values():
        for name, s, e in evs:
            for cs, ce in clip([(s, e)], lo, hi):
                per_name[_short(name)] += (ce - cs) / len(device)
    window_ns = hi - lo
    busy_ns = sum(busy_per) / len(busy_per)
    by_label = defaultdict(float)
    for g in gap_list:
        by_label[_label(g, spans)] += (g[1] - g[0]) / len(device)
    longest = sorted(gap_list, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "device_ops": [[n, s / 1e9] for n, s in
                       sorted(per_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(g, spans), (g[1] - g[0]) / 1e9]
                      for g in longest],
        "idle_by_span": {k: v / 1e9 for k, v in by_label.items()},
    }


def reduce_file(path: str | Path) -> dict:
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(str(path))
    return reduce(*events(profile))
