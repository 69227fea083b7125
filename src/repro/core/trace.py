"""Spans and counters inside the program.

**Spans.**  ``span(name, **args)`` marks one piece of host work on the
profiler's timeline as ``adhash.<name>``, with ``args`` as the event's
stats.  The switch is ``enable(on)``; while it is off, ``span`` returns one
shared ``nullcontext`` after a single boolean test, so a span site costs
nothing else.  While it is on, a span that names its request (``rid``, or
``rids`` for a bucket) hands those tags to every span opened inside it, so
the planner's or a sync's span carries the request it served.  A list value
is written space-separated (the profiler splits stats at commas).

Names, by layer (front end ``serving/loop.py``; adaptivity
``core/engine.py``; planner; executor and DSJ ``core/executor.py``,
``core/pattern_index.py`` and the sync chokepoints of ``core/substrate.py``):
``offer``, ``control``, ``bucket``, ``checkpoint``; ``pi_match``,
``adapt``, ``ird``, ``ird_wait``; ``plan``; ``execute``, ``sync``.

**Host syncs.**  Every device->host transfer on the engine's path goes
through ``sync(what)``, which bumps one process-wide monotonic count (always
on) and spans the wait as ``adhash.sync``.  ``trace_host_syncs`` reads the
count before and after a block.
"""
from __future__ import annotations

import contextlib

import jax

__all__ = ["enable", "span", "tag", "sync", "host_syncs",
           "HostSyncTrace", "trace_host_syncs"]

PREFIX = "adhash."

_on = False
_off = contextlib.nullcontext()
_tags: dict = {}          # request tags of the innermost open span
_REQUEST_TAGS = ("rid", "rids")
_syncs = 0                # device->host transfers since the process began


def enable(on: bool) -> None:
    """Turn the program's spans on or off (off at start)."""
    global _on
    _on = bool(on)


def _annotation(name: str, args: dict):
    kw = {**_tags, **args}
    for k, v in kw.items():
        if isinstance(v, (list, tuple)):
            kw[k] = " ".join(map(str, v))
    return jax.profiler.TraceAnnotation(PREFIX + name, **kw)


class _RequestSpan:
    """A span that names its request(s): spans opened inside carry them."""

    def __init__(self, name: str, args: dict):
        self.name, self.args = name, args

    def __enter__(self):
        global _tags
        self.prev = _tags
        _tags = {k: self.args[k] for k in _REQUEST_TAGS if k in self.args}
        self.ann = _annotation(self.name, self.args)
        return self.ann.__enter__()

    def __exit__(self, *exc):
        global _tags
        _tags = self.prev
        return self.ann.__exit__(*exc)


def span(name: str, **args):
    """``adhash.<name>`` around a block while tracing is on; else a shared
    no-op context."""
    if not _on:
        return _off
    if "rid" in args or "rids" in args:
        return _RequestSpan(name, args)
    return _annotation(name, args)


def tag(handle, **args) -> None:
    """Add stats known only at the end to an open span (the value ``with
    span(...) as handle`` bound); nothing while tracing is off."""
    if handle is not None:
        handle.set_metadata(**args)


def sync(what: str):
    """Count one device->host transfer, and span it while tracing."""
    global _syncs
    _syncs += 1
    if not _on:
        return _off
    return _annotation("sync", {"what": what})


def host_syncs() -> int:
    """Device->host transfers through ``sync`` since the process began."""
    return _syncs


class HostSyncTrace:
    """Device->host transfers between entering and leaving
    ``trace_host_syncs``."""

    def __init__(self) -> None:
        self._start = _syncs
        self._end: int | None = None

    @property
    def host_transfers(self) -> int:
        end = _syncs if self._end is None else self._end
        return end - self._start


@contextlib.contextmanager
def trace_host_syncs():
    """Count every host transfer issued inside the block.

    Usage::

        with trace_host_syncs() as t:
            engine.query(q)
        assert t.host_transfers == 1   # warm fast-path query
    """
    t = HostSyncTrace()
    try:
        yield t
    finally:
        t._end = _syncs
