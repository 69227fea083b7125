"""Requests through ``repro.serving.ServeLoop`` on the wall clock.

``open_loop`` offers each request at its due time, whatever the server is
doing; ``closed_loop`` keeps ``clients`` requests outstanding, each client
sending its next one as soon as its last is answered.  Latency is timed
from the due time to the moment the answer's rows are on the host.  Every
request ends in exactly one status: ``answered``, ``rejected``, ``shed``,
``unexecutable`` or, if the loop never resolved it, ``pending``.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass

import numpy as np

now = time.monotonic


@dataclass
class Req:
    rid: int
    template: str
    patterns: list              # instance: ((kind, v), (kind, v), (kind, v))
    vars: list[str]             # answer columns
    query: object               # the program's Query
    due: float = 0.0
    offered: float | None = None
    finished: float | None = None
    status: str = "pending"
    rows: np.ndarray | None = None
    mode: str = ""
    route: str = ""
    comm_cells: int = 0

    @property
    def latency(self) -> float:
        """Seconds from due to answer; infinite unless answered."""
        if self.status != "answered":
            return float("inf")
        return self.finished - self.due


@dataclass
class Spans:
    """``jax.profiler.TraceAnnotation`` around host work, when tracing."""

    on: bool = False

    def __call__(self, name: str):
        if self.on:
            import jax

            return jax.profiler.TraceAnnotation("bench." + name)
        return contextlib.nullcontext()


def _offer(loop, req: Req, spans: Spans, slo_s: float | None = None
           ) -> None:
    from repro.serving import Request

    req.offered = now()
    deadline = None if slo_s is None else req.due + slo_s
    with spans("offer"):
        verdict = loop.offer(Request(req.rid, req.query, arrival_s=req.due,
                                     deadline_s=deadline))
    if verdict is not None:
        req.status = "rejected"


def _collect(results, by_rid: dict, spans: Spans) -> list[Req]:
    """Resolve loop results: fetch each answer's rows to the host."""
    from repro.core.query import Var
    from repro.serving import ServedResult

    out = []
    for res in results:
        req = by_rid[res.rid]
        if isinstance(res, ServedResult):
            with spans("fetch"):
                req.rows = res.relation.project_to([Var(v) for v in req.vars])
            req.finished = now()
            req.status = "answered"
            req.mode, req.route = res.stats.mode, res.stats.route
            req.comm_cells = int(res.stats.comm_cells)
        else:  # SheddedResult
            req.finished = now()
            req.status = "shed" if res.reason == "deadline" else res.reason
        out.append(req)
    return out


def _sleep_until(t: float, spans: Spans) -> None:
    dt = t - now()
    if dt > 0:
        with spans("wait"):
            time.sleep(dt)


SETUP_SLO_S = 3600.0


def serve_now(loop, reqs: list[Req], spans: Spans) -> None:
    """Offer ``reqs`` at once, with a deadline no compile can miss, and
    drain: the set-up's way to run shapes."""
    by_rid = {r.rid: r for r in reqs}
    for r in reqs:
        r.due = now()
        _offer(loop, r, spans, SETUP_SLO_S)
    _collect(loop.pump() + loop.drain(), by_rid, spans)


def open_loop(loop, reqs: list[Req], spans: Spans) -> None:
    """Offer each request at ``req.due`` (absolute); drain at the end."""
    by_rid = {r.rid: r for r in reqs}
    pending = deque(sorted(reqs, key=lambda r: r.due))
    while pending:
        while pending and pending[0].due <= now():
            _offer(loop, pending.popleft(), spans)
        with spans("pump"):
            done = loop.pump()
        _collect(done, by_rid, spans)
        if not pending:
            break
        nxt = pending[0].due
        due = loop.next_due()
        _sleep_until(nxt if due is None else min(nxt, due), spans)
    with spans("drain"):
        done = loop.drain()
    _collect(done, by_rid, spans)


def closed_loop(loop, make_req, clients: int, close: float,
                spans: Spans) -> list[Req]:
    """``clients`` outstanding requests until ``close``; each answered (or
    shed) request is followed at once by its client's next, while the
    window is open.  The loop has no other arrivals, so when nothing is
    due the clients all wait and the server drains its buckets."""
    by_rid: dict[int, Req] = {}
    sent: list[Req] = []

    def issue():
        r = make_req()
        r.due = now()
        by_rid[r.rid] = r
        sent.append(r)
        _offer(loop, r, spans)
        return r

    outstanding = {issue().rid for _ in range(clients)}
    outstanding -= {r.rid for r in sent if r.status == "rejected"}
    while outstanding:
        with spans("pump"):
            done = loop.pump()
        if not done:
            with spans("drain"):
                done = loop.drain()
            if not done:
                break  # nothing the loop will resolve: left pending
        for r in _collect(done, by_rid, spans):
            outstanding.discard(r.rid)
            if now() < close:
                nxt = issue()
                if nxt.status != "rejected":
                    outstanding.add(nxt.rid)
    return sent
