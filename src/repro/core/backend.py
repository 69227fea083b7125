"""Data-plane backend registry + static-capacity classes (DSJ hot-loop
plumbing).

Every hot operation in the DSJ data plane is one of four vectorized
primitives: a sorted-search *probe* (match ranges for a block of keys), a
join *expansion* (materialize variable-multiplicity ranges), a *projection
compaction* (sort-dedupe-compact), and a per-destination *bucketing* (build
all_to_all send buffers).  This module is the single place that decides
*how* each of them runs:

  ``searchsorted``  the plain-jnp path — binary searches and argsorts, the
                    default on CPU/GPU where data-dependent gathers are
                    cheap.
  ``pallas``        the default on TPU: the semijoin probe kernel
                    (``repro.kernels.semijoin``) inside its work envelope
                    and the jnp binary search past it; the fused jnp
                    mirrors of the relalg kernels for the other three
                    primitives (``repro.kernels.relalg_ops`` says why).
                    ``resolved_impls`` records every choice.
  ``auto``          resolved once per process to one of the two above.

Implementations self-register via :func:`register_impl`; the providers are
imported lazily on first dispatch so importing this module stays cheap.  One
backend name selects the whole data plane — ``AdHashEngine(
data_plane_backend=...)`` (alias ``probe_backend``) threads it into every
jitted stage as a static argument.  Resolution is routed through the
execution substrate (``Substrate.resolve_backend``), and the resolved name
reaches the stage bodies *inside* ``shard_map`` on a mesh substrate — i.e.
the Pallas kernels run per shard, against local worker blocks.

The second half of the module is the static-shape discipline that keeps the
jit cache warm: every dynamic capacity (planner hints, retry doubling, user
capacities) is quantized to a power-of-two class via ``quantize_capacity``,
so repeated queries of the same shape reuse compiled stages instead of
triggering a per-query recompilation storm.  See DESIGN.md §4.
"""
from __future__ import annotations

import collections
import importlib
from typing import Callable

import jax
import jax.numpy as jnp

__all__ = [
    "DATA_PLANE_BACKENDS",
    "PROBE_BACKENDS",
    "default_backend",
    "resolve_backend",
    "register_impl",
    "get_impl",
    "range_search",
    "span_search",
    "quantize_capacity",
    "probe_compile_cache_size",
    "note_impl",
    "resolved_impls",
]

DATA_PLANE_BACKENDS = ("searchsorted", "pallas")
# historical name from the probe-only dispatcher era; same tuple
PROBE_BACKENDS = DATA_PLANE_BACKENDS


# ---------------------------------------------------------------- resolution
def default_backend() -> str:
    """Platform-detected backend: Pallas on TPU, searchsorted elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "searchsorted"


def resolve_backend(name: str | None) -> str:
    """Resolve 'auto'/None to a concrete backend and validate the name.

    Resolving happens host-side, once, so the concrete name is what reaches
    the jitted stages as a static argument (stable jit cache keys)."""
    if name is None or name == "auto":
        return default_backend()
    if name not in DATA_PLANE_BACKENDS:
        raise ValueError(
            f"unknown data-plane backend {name!r}; expected one of "
            f"{DATA_PLANE_BACKENDS + ('auto',)}"
        )
    return name


# ------------------------------------------------------------------ registry
# (op, backend) -> implementation.  Providers self-register at import time;
# the lazy import below pulls a provider in on the first dispatch so that
# e.g. the kernels package is only loaded when a pallas impl is requested.
_IMPLS: dict[tuple[str, str], Callable] = {}
_PROVIDERS = {
    "searchsorted": "repro.core.relalg",
    "pallas": "repro.kernels.relalg_ops",
}


def register_impl(op: str, backend: str):
    """Decorator: register ``fn`` as the ``backend`` impl of primitive
    ``op`` (e.g. ``@register_impl("expand", "pallas")``)."""
    if backend not in DATA_PLANE_BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")

    def deco(fn: Callable) -> Callable:
        _IMPLS[(op, backend)] = fn
        return fn

    return deco


def get_impl(op: str, backend: str) -> Callable:
    """Look up the registered implementation, importing its provider module
    on first use.  Called at trace time, so dispatch costs nothing at run
    time and the choice is baked into the jit cache key via ``backend``."""
    key = (op, backend)
    if key not in _IMPLS:
        provider = _PROVIDERS.get(backend)
        if provider is None:
            raise ValueError(f"unknown data-plane backend {backend!r}")
        importlib.import_module(provider)
    try:
        return _IMPLS[key]
    except KeyError:
        raise KeyError(
            f"no {backend!r} implementation registered for {op!r}; "
            f"registered: {sorted(_IMPLS)}"
        ) from None


# --------------------------------------------------------- dispatch record
# Which implementation each primitive of the ``pallas`` backend resolved to:
# the Pallas kernel, or the jnp implementation past the kernel's envelope
# (or off-TPU).  The choice depends on static shapes only, so it is made and
# counted once per traced call site — per compiled program, not per run.
_RESOLVED: collections.Counter = collections.Counter()


def note_impl(op: str, impl: str) -> None:
    _RESOLVED[(op, impl)] += 1


def resolved_impls() -> dict[str, dict[str, int]]:
    """{primitive: {"kernel" | "jnp": traced call sites}} so far."""
    out: dict[str, dict[str, int]] = {}
    for (op, impl), n in sorted(_RESOLVED.items()):
        out.setdefault(op, {})[impl] = n
    return out


# ------------------------------------------------------------------- probes
# Below this many probe keys the O(N) masked-compare kernel cannot beat two
# binary searches (a scalar oracle probe would scan the whole shard), so tiny
# probe blocks stay on searchsorted on every backend; so does every probe
# past the kernel's work envelope.  Static shapes make the choice
# trace-time; results are identical either way.
_MIN_PALLAS_PROBES = 16


def _probe_kernel(backend: str, n_keys: int, n_probes: int):
    """The semijoin kernel where the pallas backend should run it, else
    None (the jnp binary search runs)."""
    if backend != "pallas":
        return None
    from repro.kernels.semijoin.semijoin import probe_fits, semijoin_probe

    use = n_probes >= _MIN_PALLAS_PROBES and probe_fits(n_keys, n_probes)
    note_impl("probe", "kernel" if use else "jnp")
    return semijoin_probe if use else None


def range_search(
    keys: jax.Array,  # (N,) sorted, max-padded
    probes: jax.Array,  # (M,)
    backend: str = "searchsorted",
) -> tuple[jax.Array, jax.Array]:
    """Match range [lo, hi) of each probe key: side-left / side-right
    ``searchsorted`` — the canonical semi-join probe op.  Both int32."""
    kernel = _probe_kernel(backend, keys.shape[0], probes.shape[0])
    if kernel is not None:
        return kernel(keys, probes)
    lo = jnp.searchsorted(keys, probes, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(keys, probes, side="right").astype(jnp.int32)
    return lo, hi


def span_search(
    keys: jax.Array,  # (N,) sorted, max-padded
    lo_keys: jax.Array,  # (M,)
    hi_keys: jax.Array,  # (M,)
    backend: str = "searchsorted",
) -> tuple[jax.Array, jax.Array]:
    """Side-left insertion points of two probe arrays at once — the
    [lo_key, hi_key) composite-key span form used by range scans (P-index
    ranges, variable predicates)."""
    m = lo_keys.shape[0]
    kernel = _probe_kernel(backend, keys.shape[0], 2 * m)
    if kernel is not None:
        lo_both, _ = kernel(keys, jnp.concatenate([lo_keys, hi_keys]))
        return lo_both[:m], lo_both[m:]
    lo = jnp.searchsorted(keys, lo_keys, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(keys, hi_keys, side="left").astype(jnp.int32)
    return lo, hi


# ------------------------------------------------------- capacity quantizing
def quantize_capacity(n: int | float, floor: int = 64,
                      ceil: int | None = None) -> int:
    """Round a capacity up to its power-of-two class (min ``floor``).

    Static shapes bake capacities into jit cache keys; arbitrary per-query
    capacities (e.g. ``2 * estimated_cardinality``) would recompile every
    stage on every query.  Power-of-two classes collapse the key space so a
    warm workload reuses compiled stages.  ``ceil`` (optional, also a power
    of two) caps planner *hints* only — retry doubling must stay unbounded
    or overflow recovery would live-lock."""
    n = max(int(n), floor, 1)
    q = 1 << (n - 1).bit_length()
    if ceil is not None:
        q = min(q, ceil)
    return q


# ------------------------------------------------------------- observability
def probe_compile_cache_size() -> int:
    """Total jit-cache entries across the DSJ data-plane entry points —
    probes *and* relalg kernels.

    Used by the recompilation regression tests and ``bench_probe`` /
    ``bench_relalg``: after warmup, repeated same-shape queries must not
    grow this number."""
    from . import dsj, triples

    fns = [
        triples.match_ranges,
        triples.probe_values,
        triples.gather_rows,
        dsj.match_rows,
        dsj.match_first,
        dsj.project_unique,
        dsj.exchange_hash,
        dsj.exchange_broadcast,
        dsj.probe_and_reply,
        dsj.finalize_join,
        dsj.local_probe_join,
        dsj.local_chain,
        dsj.local_chain_from,
        dsj.local_chain_batch,
        dsj.local_chain_from_batch,
        dsj.match_first_batch,
        dsj.project_unique_batch,
        dsj.exchange_hash_batch,
        dsj.exchange_broadcast_batch,
        dsj.probe_and_reply_batch,
        dsj.finalize_join_batch,
        dsj.local_probe_join_batch,
    ]
    try:  # the relalg kernel wrappers are data-plane entry points too
        from repro.kernels.relalg_ops import ops as relalg_ops_ops

        fns += [
            relalg_ops_ops.batched_expand,
            relalg_ops_ops.batched_bucket_by_dest,
            relalg_ops_ops.batched_unique_compact,
        ]
    except ImportError:  # pragma: no cover - kernels package unavailable
        pass
    # the mesh-substrate stage wrappers are entry points of their own: the
    # sharded path is held to the same zero-recompile standard
    from . import substrate as _substrate

    fns += list(_substrate.SHARDED_STAGE_FNS)
    # IRD's fused replica-indexing dispatch (created lazily on first
    # redistribution) — repeated redistributions of same-shape patterns
    # must reuse its cache like any other stage
    from . import ird as _ird

    if _ird._INDEX_ROWS_JIT is not None:
        fns.append(_ird._INDEX_ROWS_JIT)
    # _cache_size is a private jit API with no stability guarantee; degrade
    # to 0 (metric unavailable) rather than crash on a jax version bump
    return sum(getattr(f, "_cache_size", lambda: 0)() for f in fns)
