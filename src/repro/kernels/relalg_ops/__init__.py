"""Data-plane kernels for the relalg primitives, and the ``pallas``
provider of the data-plane backend registry (``repro.core.backend``).

  expand          cumsum + range-materialize in one grid pass   (expand.py)
  bucket_by_dest  count-then-place layout, no argsort           (bucket.py)
  unique_compact  fused bitonic sort-dedupe-compact             (compact.py)

The three kernels compile for TPU v5e (tests/test_tpu_compile.py) and
match their references in interpret mode (tests/test_relalg_kernels.py),
but none of them beat the jnp implementation on one v5e chip (8 workers,
4,096 rows: expand 23.9 ms against 7.5 ms, bucket 4.3 ms against 1.1 ms,
compact 0.58 ms against 0.64 ms; see PERF.md).  So the ``pallas`` backend
registers the fused jnp mirrors in ``repro.core.relalg`` for these
primitives, by name, and records each choice in
``repro.core.backend.resolved_impls``; only the semijoin probe runs its
kernel.  A kernel goes back into the registry once it measurably beats its
mirror on the chip.
"""
from __future__ import annotations

from repro.core import backend as _backend
from repro.core import relalg as _relalg

from .bucket import bucket_by_dest_pallas
from .compact import unique_compact_pallas
from .expand import expand_pallas

__all__ = [
    "expand_pallas",
    "bucket_by_dest_pallas",
    "unique_compact_pallas",
]


@_backend.register_impl("expand", "pallas")
def _expand(lo, hi, out_cap):
    _backend.note_impl("expand", "jnp")
    return _relalg.expand_fused(lo, hi, out_cap)


@_backend.register_impl("bucket_by_dest", "pallas")
def _bucket_by_dest(values, dest, valid, n_dest, cap_peer, pad=-1):
    _backend.note_impl("bucket_by_dest", "jnp")
    return _relalg.bucket_by_dest_counting(values, dest, valid, n_dest,
                                           cap_peer, pad)


@_backend.register_impl("unique_compact", "pallas")
def _unique_compact(values, valid, out_cap, pad):
    _backend.note_impl("unique_compact", "jnp")
    return _relalg.unique_compact_fused(values, valid, out_cap, pad)


# Fused case-(i) chain bodies (main-index subject stars, DESIGN.md §11).
# The chain is a composition of stages whose primitives already dispatch
# through this registry, so the pallas impl reuses the reference composition
# from dsj with the backend name threaded into every primitive, inside one
# shard_map body.  A future optimization can re-register a true single-grid-pass kernel here
# (probe -> expand -> filter fused) without touching any caller.
@_backend.register_impl("local_chain", "pallas")
def _local_chain(store, consts, first_spec, first_keep, steps, caps,
                 backend):
    from repro.core.dsj import _local_chain_body

    return _local_chain_body(store, consts, first_spec, first_keep, steps,
                             caps, backend)


@_backend.register_impl("local_chain_from", "pallas")
def _local_chain_from(store, rel_cols, rel_valid, consts, steps, caps,
                      backend):
    from repro.core.dsj import _local_chain_from_body

    return _local_chain_from_body(store, rel_cols, rel_valid, consts, steps,
                                  caps, backend)
