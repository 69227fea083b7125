"""Execution substrate: the device mesh under the DSJ data plane.

The stages in ``dsj.py`` are *global-view* functions over arrays with a
leading worker axis W.  A substrate decides where that axis physically lives
and how the stage-internal worker exchanges are realized:

``SingleDeviceSubstrate`` (the default)
    W lives on one device; the exchanges stay the in-memory block transposes
    / broadcasts of dsj.py.  Delegates to the exact module-level jitted
    stages, so an engine built without a substrate behaves — jit cache
    included — exactly as before this layer existed.

``MeshSubstrate``
    W is sharded over the ``data`` axis of a real ``jax.sharding.Mesh``
    (device d owns the contiguous worker block ``[d*W/D, (d+1)*W/D)``).
    Every stage is wrapped in ``shard_map``: the per-worker bodies run
    unchanged on the local worker block, while the (W_sender, W_receiver)
    block transposes of ``exchange_hash`` / the candidate reply route are
    expressed as ``jax.lax.all_to_all`` and the sender-axis broadcast of
    ``exchange_broadcast`` as ``jax.lax.all_gather`` — the paper's hash
    distribution vs. broadcast dichotomy (Observation 1), lowered to the
    matching XLA collectives (asserted on the compiled HLO in
    tests/test_substrate_mesh.py).  Per-shard overflow totals are ``pmax``-ed
    and per-shard wire-cell counts ``psum``-ed back to replicated scalars, so
    the host-side retry protocol and the per-query ``QueryStats``
    communication accounting are bit-identical to the single-device path.
    The batched ``*_batch`` stages keep the batch axis B *replicated* (specs
    ``P(None, 'data')``): one collective launch is amortized over the whole
    shape bucket — B queries share one all_to_all instead of issuing B.

    **Shard-local route** (the dual of the collectives above): the two
    stages parallel mode is made of — ``match_first`` and
    ``local_probe_join`` — have second wrappers with *no* cross-shard
    reductions at all.  The regular mesh wrappers ``pmax`` the per-shard
    overflow totals back to a replicated scalar, which lowers to an
    all-reduce; a PI-hit query provably needs no communication (the paper's
    parallel mode — IRD already collocated every replica module), so paying
    even that reduction is pure overhead.  The ``*_local`` wrappers instead
    return the per-shard totals as a ``P('data')``-sharded ``(D,)`` vector
    and let the host take the max while deciding the overflow retry — a sync
    it performs anyway.  Their compiled HLO contains **zero** collectives
    (asserted in tests/test_substrate_mesh.py, the mirror image of the
    all_to_all/all_gather assertions).

Sharding layout (PartitionSpecs) for the stage operands:

    store leaves   (W, capT, …)        P('data')      one shard block/device
    relations      (W, cap, k)         P('data')
    projections    (W, cap_proj)       P('data')
    recv/cand      (W, W_peer, cap, …) P('data')      peer axis replicated
    replica module (W, capR, …)        P('data')      placed by shard_store
    batched forms  (B, W, …)           P(None, 'data')
    pattern consts (3,) / (B, 3)       P()            replicated
    totals/cells   scalars / (B,)      P()            pmax/psum-replicated
    local totals   (D,)                P('data')      shard-local route only

All sharded wrappers are module-level ``jit`` functions with the mesh as a
static argument, so they share one compile cache (counted by
``backend.probe_compile_cache_size``) and the power-of-two capacity classes
keep warmed sharded workloads recompile-free exactly like the single-device
path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.compat import fetch_global, shard_map

from . import dsj, trace
from .backend import resolve_backend
from .relation import Relation
from .trace import HostSyncTrace, trace_host_syncs
from .triples import ShardedTripleStore, match_ranges

__all__ = ["Substrate", "SingleDeviceSubstrate", "MeshSubstrate",
           "DistributedSubstrate", "WORKER_AXIS", "host_total",
           "host_chain_totals", "host_fetch", "HostSyncTrace",
           "trace_host_syncs"]

WORKER_AXIS = "data"


# ===========================================================================
# Substrate API
# ===========================================================================
class Substrate:
    """Base substrate: the single-device global view (today's behavior).

    An executor only ever talks to the data plane through a substrate's
    stage methods; the base class binds them straight to the module-level
    jitted stages in dsj.py / triples.py (zero indirection cost, same jit
    cache), so ``Substrate()`` is a faithful stand-in for the pre-substrate
    engine.
    """

    name = "single"
    n_devices = 1
    # multi-process topology (DESIGN §12): one process holding every device
    # unless a DistributedSubstrate overrides these from jax.distributed
    n_processes = 1
    process_id = 0

    # ----------------------------------------------------------- resolution
    def resolve_backend(self, name: str | None) -> str:
        """Per-substrate data-plane backend resolution.

        The concrete name is threaded into every stage as a static argument,
        so whatever this returns is what runs *inside* the per-shard body —
        on a TPU mesh the Pallas kernels execute per shard."""
        return resolve_backend(name)

    def check_workers(self, n_workers: int) -> None:
        """Validate that a worker count is placeable on this substrate."""

    # ------------------------------------------------------------ placement
    def shard_store(self, store: ShardedTripleStore) -> ShardedTripleStore:
        return store

    def shard_relation(self, rel: Relation) -> Relation:
        return rel

    # ------------------------------------------- host-sharded loading (§12)
    # The out-of-core ingest path builds worker shards host-side and places
    # them through these three hooks instead of materializing a global array
    # per process: ``local_worker_slice`` names the contiguous worker block
    # this process is responsible for, ``globalize_worker_array`` assembles a
    # (possibly cross-process) global device array from that local block, and
    # ``barrier`` fences bootstrap phases.  Single-process substrates load
    # every worker locally, so the hooks degenerate to jnp.asarray.
    def local_worker_slice(self, n_workers: int) -> slice:
        """Contiguous worker block this process loads ([0, W) here)."""
        self.check_workers(n_workers)
        return slice(0, n_workers)

    def globalize_worker_array(self, local: np.ndarray, n_workers: int):
        """Device array with global leading axis ``n_workers`` built from
        this process's ``local_worker_slice`` block."""
        return jnp.asarray(local)

    def barrier(self, tag: str = "barrier") -> None:
        """Cross-process rendezvous (no-op off a multi-process mesh)."""

    # -------------------------------------------------------------- stages
    match_ranges = staticmethod(match_ranges)
    match_rows = staticmethod(dsj.match_rows)
    match_first = staticmethod(dsj.match_first)
    project_unique = staticmethod(dsj.project_unique)
    exchange_hash = staticmethod(dsj.exchange_hash)
    exchange_broadcast = staticmethod(dsj.exchange_broadcast)
    probe_and_reply = staticmethod(dsj.probe_and_reply)
    finalize_join = staticmethod(dsj.finalize_join)
    local_probe_join = staticmethod(dsj.local_probe_join)
    match_first_batch = staticmethod(dsj.match_first_batch)
    project_unique_batch = staticmethod(dsj.project_unique_batch)
    exchange_hash_batch = staticmethod(dsj.exchange_hash_batch)
    exchange_broadcast_batch = staticmethod(dsj.exchange_broadcast_batch)
    probe_and_reply_batch = staticmethod(dsj.probe_and_reply_batch)
    finalize_join_batch = staticmethod(dsj.finalize_join_batch)
    local_probe_join_batch = staticmethod(dsj.local_probe_join_batch)
    # Shard-local route (parallel mode over collocated replicas): on one
    # device "no cross-shard communication" is vacuously true, so the local
    # stages ARE the regular stages — same functions, same jit cache.  The
    # overflow total may come back as any (possibly per-shard) array; hosts
    # reduce it with ``host_total``.
    match_first_local = staticmethod(dsj.match_first)
    local_probe_join_local = staticmethod(dsj.local_probe_join)
    # Fused case-(i) chains (main-index subject stars, DESIGN.md §11): whole
    # query in one dispatch.  Single-device, the chain functions ARE the
    # fast route — per-stage totals come back stacked and the host syncs
    # once per query, exactly like the mesh wrappers below.
    local_chain = staticmethod(dsj.local_chain)
    local_chain_from = staticmethod(dsj.local_chain_from)
    local_chain_batch = staticmethod(dsj.local_chain_batch)
    local_chain_from_batch = staticmethod(dsj.local_chain_from_batch)


# ---------------------------------------------------------------------------
# Host sync chokepoints.  Every device->host transfer the executor performs
# funnels through one of the three helpers below, each counted by
# ``repro.core.trace.sync`` (``trace_host_syncs`` reads the count around a
# block), so the roofline audit and the one-sync-per-warm-query acceptance
# test count actual syncs — no guessing from profiler output.
# ---------------------------------------------------------------------------
def host_total(total) -> int:
    """Host-side max of a stage overflow total.

    Regular stages return a replicated scalar (pmax-ed on a mesh); the
    shard-local stages return the per-shard maxima as a ``(D,)`` vector and
    skip the on-device reduction — the host takes the max during the
    overflow-retry check, a sync point it hits regardless.  Under a
    multi-process mesh the fetch routes through ``fetch_global`` (the
    per-shard vector spans processes); every process performs it in
    lockstep, so the retry decision is replicated by construction."""
    with trace.sync("total"):
        return int(np.max(fetch_global(total)))


def host_chain_totals(totals) -> np.ndarray:
    """One host sync for a whole fused chain: per-stage overflow maxima.

    ``totals`` is stage-major — (S,) single-device, (S, D) shard-local mesh,
    (S, B) batched single-device or (S, B, D) batched mesh.  Everything
    after the stage axis is reduced away (capacity classes are shared across
    the batch, like the sequential batch retry), so the result is always an
    (S,) int vector.  This is THE one device->host transfer of a warm
    fast-path query."""
    with trace.sync("chain_totals"):
        arr = fetch_global(totals)
    return arr.reshape(arr.shape[0], -1).max(axis=1)


def host_fetch(x, what: str = "fetch") -> np.ndarray:
    """Materialize a device array on the host (result/accounting fetch)."""
    with trace.sync(what):
        return fetch_global(x)


class SingleDeviceSubstrate(Substrate):
    """Explicit name for the default substrate."""


class MeshSubstrate(Substrate):
    """Worker axis W sharded over the ``data`` axis of a device mesh."""

    name = "mesh"

    def __init__(
        self,
        mesh: Mesh | None = None,
        *,
        axis: str = WORKER_AXIS,
        devices=None,
    ):
        if mesh is None:
            devs = list(devices) if devices is not None else jax.devices()
            mesh = Mesh(np.array(devs), (axis,))
        if axis not in mesh.axis_names:
            raise ValueError(
                f"mesh has no {axis!r} axis (axes: {mesh.axis_names})"
            )
        self.mesh = mesh
        self.axis = axis
        self.n_devices = int(mesh.shape[axis])

    def check_workers(self, n_workers: int) -> None:
        if n_workers % self.n_devices:
            raise ValueError(
                f"n_workers={n_workers} must be divisible by the mesh "
                f"{self.axis!r} axis size {self.n_devices} (each device owns "
                f"a contiguous block of workers)"
            )

    # ------------------------------------------------------------ placement
    def worker_sharding(self, n_leading_batch: int = 0) -> NamedSharding:
        """NamedSharding placing the worker axis (after ``n_leading_batch``
        replicated batch axes) on the mesh ``data`` axis."""
        spec = PartitionSpec(*([None] * n_leading_batch), self.axis)
        return NamedSharding(self.mesh, spec)

    def shard_store(self, store: ShardedTripleStore) -> ShardedTripleStore:
        self.check_workers(store.n_workers)
        return store.device_put(self.worker_sharding())

    def shard_relation(self, rel: Relation) -> Relation:
        self.check_workers(rel.n_workers)
        return rel.device_put(self.worker_sharding())

    def globalize_worker_array(self, local, n_workers: int):
        # single process: the local block IS the global array
        return jax.device_put(local, self.worker_sharding())

    # -------------------------------------------------------------- stages
    # Thin bindings to the module-level jitted wrappers below; mesh/axis ride
    # along as static arguments so all MeshSubstrate instances over the same
    # mesh share one compile cache.
    def match_ranges(self, store, p_const, sk_const, *, use_po, nid,
                     backend="searchsorted"):
        return _match_ranges_sharded(self.mesh, self.axis, store, p_const,
                                     sk_const, use_po=use_po, nid=nid,
                                     backend=backend)

    def match_rows(self, store, consts, spec, cap_out,
                   backend="searchsorted"):
        return _match_rows_sharded(self.mesh, self.axis, store, consts,
                                   spec=spec, cap_out=cap_out,
                                   backend=backend)

    def match_first(self, store, consts, spec, cap_out,
                    backend="searchsorted"):
        return _match_first_sharded(self.mesh, self.axis, store, consts,
                                    spec=spec, cap_out=cap_out,
                                    backend=backend)

    def project_unique(self, cols, valid, col_idx, cap_proj,
                       backend="searchsorted"):
        return _project_unique_sharded(self.mesh, self.axis, cols, valid,
                                       col_idx=col_idx, cap_proj=cap_proj,
                                       backend=backend)

    def exchange_hash(self, proj, proj_valid, cap_peer,
                      backend="searchsorted", spec=None, table=None):
        return _exchange_hash_sharded(self.mesh, self.axis, proj, proj_valid,
                                      cap_peer=cap_peer, backend=backend,
                                      pspec=spec, table=table)

    def exchange_broadcast(self, proj, proj_valid):
        return _exchange_broadcast_sharded(self.mesh, self.axis, proj,
                                           proj_valid)

    def probe_and_reply(self, store, recv, recv_valid, consts, spec,
                        probe_col, cap_flat, cap_cand,
                        backend="searchsorted"):
        return _probe_and_reply_sharded(
            self.mesh, self.axis, store, recv, recv_valid, consts, spec=spec,
            probe_col=probe_col, cap_flat=cap_flat, cap_cand=cap_cand,
            backend=backend,
        )

    def finalize_join(self, rel_cols, rel_valid, cand, cand_valid,
                      join_col_rel, probe_col, shared_checks, append_cols,
                      cap_out, backend="searchsorted"):
        return _finalize_join_sharded(
            self.mesh, self.axis, rel_cols, rel_valid, cand, cand_valid,
            join_col_rel=join_col_rel, probe_col=probe_col,
            shared_checks=shared_checks, append_cols=append_cols,
            cap_out=cap_out, backend=backend,
        )

    def local_probe_join(self, store, rel_cols, rel_valid, consts, spec,
                         join_col_rel, probe_col, shared_checks, append_cols,
                         cap_out, backend="searchsorted"):
        return _local_probe_join_sharded(
            self.mesh, self.axis, store, rel_cols, rel_valid, consts,
            spec=spec, join_col_rel=join_col_rel, probe_col=probe_col,
            shared_checks=shared_checks, append_cols=append_cols,
            cap_out=cap_out, backend=backend,
        )

    # ------------------------------------------------- shard-local route
    # Parallel mode over IRD-collocated replica modules: the same bodies as
    # the wrappers above, with the pmax total-reductions dropped — the
    # compiled HLO contains zero cross-shard collectives (the acceptance
    # assertion of the shard-local route).
    def match_first_local(self, store, consts, spec, cap_out,
                          backend="searchsorted"):
        return _match_first_shardlocal(self.mesh, self.axis, store, consts,
                                       spec=spec, cap_out=cap_out,
                                       backend=backend)

    def local_probe_join_local(self, store, rel_cols, rel_valid, consts,
                               spec, join_col_rel, probe_col, shared_checks,
                               append_cols, cap_out, backend="searchsorted"):
        return _local_probe_join_shardlocal(
            self.mesh, self.axis, store, rel_cols, rel_valid, consts,
            spec=spec, join_col_rel=join_col_rel, probe_col=probe_col,
            shared_checks=shared_checks, append_cols=append_cols,
            cap_out=cap_out, backend=backend,
        )

    # Fused case-(i) chains: one shard_map body per query shape covering
    # match_first + every local join — zero cross-shard collectives, totals
    # come back as a P('data')-sharded stage-major matrix for the host's
    # single end-of-chain sync (``host_chain_totals``).
    def local_chain(self, store, consts, first_spec, first_keep, steps, caps,
                    backend="searchsorted"):
        return _local_chain_shardlocal(
            self.mesh, self.axis, store, consts, first_spec=first_spec,
            first_keep=first_keep, steps=steps, caps=caps, backend=backend,
        )

    def local_chain_from(self, store, rel_cols, rel_valid, consts, steps,
                         caps, backend="searchsorted"):
        return _local_chain_from_shardlocal(
            self.mesh, self.axis, store, rel_cols, rel_valid, consts,
            steps=steps, caps=caps, backend=backend,
        )

    def local_chain_batch(self, store, consts, first_spec, first_keep, steps,
                          caps, backend="searchsorted"):
        return _local_chain_batch_shardlocal(
            self.mesh, self.axis, store, consts, first_spec=first_spec,
            first_keep=first_keep, steps=steps, caps=caps, backend=backend,
        )

    def local_chain_from_batch(self, store, rel_cols, rel_valid, consts,
                               steps, caps, backend="searchsorted"):
        return _local_chain_from_batch_shardlocal(
            self.mesh, self.axis, store, rel_cols, rel_valid, consts,
            steps=steps, caps=caps, backend=backend,
        )

    def match_first_batch(self, store, consts, spec, cap_out,
                          backend="searchsorted"):
        return _match_first_batch_sharded(self.mesh, self.axis, store, consts,
                                          spec=spec, cap_out=cap_out,
                                          backend=backend)

    def project_unique_batch(self, cols, valid, col_idx, cap_proj,
                             backend="searchsorted"):
        return _project_unique_batch_sharded(
            self.mesh, self.axis, cols, valid, col_idx=col_idx,
            cap_proj=cap_proj, backend=backend,
        )

    def exchange_hash_batch(self, proj, proj_valid, cap_peer,
                            backend="searchsorted", spec=None, table=None):
        return _exchange_hash_batch_sharded(self.mesh, self.axis, proj,
                                            proj_valid, cap_peer=cap_peer,
                                            backend=backend, pspec=spec,
                                            table=table)

    def exchange_broadcast_batch(self, proj, proj_valid):
        return _exchange_broadcast_batch_sharded(self.mesh, self.axis, proj,
                                                 proj_valid)

    def probe_and_reply_batch(self, store, recv, recv_valid, consts, spec,
                              probe_col, cap_flat, cap_cand,
                              backend="searchsorted"):
        return _probe_and_reply_batch_sharded(
            self.mesh, self.axis, store, recv, recv_valid, consts, spec=spec,
            probe_col=probe_col, cap_flat=cap_flat, cap_cand=cap_cand,
            backend=backend,
        )

    def finalize_join_batch(self, rel_cols, rel_valid, cand, cand_valid,
                            join_col_rel, probe_col, shared_checks,
                            append_cols, cap_out, backend="searchsorted"):
        return _finalize_join_batch_sharded(
            self.mesh, self.axis, rel_cols, rel_valid, cand, cand_valid,
            join_col_rel=join_col_rel, probe_col=probe_col,
            shared_checks=shared_checks, append_cols=append_cols,
            cap_out=cap_out, backend=backend,
        )

    def local_probe_join_batch(self, store, rel_cols, rel_valid, consts,
                               spec, join_col_rel, probe_col, shared_checks,
                               append_cols, cap_out, backend="searchsorted"):
        return _local_probe_join_batch_sharded(
            self.mesh, self.axis, store, rel_cols, rel_valid, consts,
            spec=spec, join_col_rel=join_col_rel, probe_col=probe_col,
            shared_checks=shared_checks, append_cols=append_cols,
            cap_out=cap_out, backend=backend,
        )


class DistributedSubstrate(MeshSubstrate):
    """MeshSubstrate over a multi-host mesh via ``jax.distributed`` (§12).

    The data plane is *unchanged*: the same module-level sharded stage
    wrappers run over a mesh whose devices now span processes, so the
    all_to_all / all_gather lowering, the zero-collective shard-local route,
    the fused chains and the jit cache discipline all carry over verbatim.
    What this class adds is the *host side* of multi-process SPMD:

      * bring-up — ``repro.launch.multihost.init_from_env`` joins the
        coordinator (args or the ``ADHASH_*`` env protocol) before the first
        backend touch, then the mesh is built over ``jax.devices()``, which
        now lists every process's devices;
      * host-sharded loading — ``local_worker_slice`` exposes the contiguous
        worker block whose devices live in this process, and
        ``globalize_worker_array`` assembles global arrays from per-process
        blocks (``jax.make_array_from_process_local_data``), so ingest
        device_puts only 1/P of the store per host;
      * host fetches — ``shard_store`` / ``shard_relation`` recognise
        already-global (non-fully-addressable) arrays and pass them through;
        everything host-bound funnels through ``fetch_global``.

    Every host-side control decision (overflow retries, adaptivity, query
    routing) consumes replicated or allgathered values, so all processes
    issue identical collective sequences — the SPMD lockstep contract the
    parity suite asserts.

    With no coordinator configured this degenerates to a single-process
    ``MeshSubstrate`` over the local devices (n_processes == 1), which keeps
    the fast in-process tests meaningful."""

    name = "distributed"

    def __init__(
        self,
        mesh: Mesh | None = None,
        *,
        axis: str = WORKER_AXIS,
        devices=None,
        coordinator: str | None = None,
        num_processes: int | None = None,
        process_id: int | None = None,
    ):
        from repro.launch.multihost import init_from_env

        init_from_env(coordinator=coordinator, num_processes=num_processes,
                      process_id=process_id)
        super().__init__(mesh, axis=axis, devices=devices)
        self.n_processes = jax.process_count()
        self.process_id = jax.process_index()

    def check_workers(self, n_workers: int) -> None:
        super().check_workers(n_workers)
        if n_workers % max(self.n_processes, 1):
            raise ValueError(
                f"n_workers={n_workers} must be divisible by the process "
                f"count {self.n_processes} (each process loads a contiguous "
                f"worker block)"
            )

    # ------------------------------------------- host-sharded loading (§12)
    def local_worker_slice(self, n_workers: int) -> slice:
        """Worker block whose devices are addressable from this process."""
        self.check_workers(n_workers)
        amap = self.worker_sharding().addressable_devices_indices_map(
            (n_workers,)
        )
        starts = [idx[0].start or 0 for idx in amap.values()]
        stops = [
            n_workers if idx[0].stop is None else idx[0].stop
            for idx in amap.values()
        ]
        lo, hi = min(starts), max(stops)
        if hi - lo != n_workers // self.n_processes:
            raise AssertionError(
                f"process-local worker block [{lo}, {hi}) is not the "
                f"contiguous 1/{self.n_processes} slice of W={n_workers}"
            )
        return slice(lo, hi)

    def globalize_worker_array(self, local, n_workers: int):
        local = np.asarray(local)
        return jax.make_array_from_process_local_data(
            self.worker_sharding(), local, (n_workers,) + local.shape[1:]
        )

    def shard_store(self, store: ShardedTripleStore) -> ShardedTripleStore:
        # device-rebuilt stores (IRD replica modules / rebalances) are
        # already global arrays spanning processes — re-placing them would
        # require a host round-trip no process can perform alone
        if isinstance(store.spo_ps, jax.Array) \
                and not store.spo_ps.is_fully_addressable:
            return store
        self.check_workers(store.n_workers)
        sl = self.local_worker_slice(store.n_workers)
        leaves, aux = store.tree_flatten()
        placed = tuple(
            self.globalize_worker_array(np.asarray(x)[sl], store.n_workers)
            for x in leaves
        )
        return ShardedTripleStore.tree_unflatten(aux, placed)

    def shard_relation(self, rel: Relation) -> Relation:
        if isinstance(rel.cols, jax.Array) \
                and not rel.cols.is_fully_addressable:
            return rel
        self.check_workers(rel.n_workers)
        sl = self.local_worker_slice(rel.n_workers)
        return Relation(
            self.globalize_worker_array(np.asarray(rel.cols)[sl],
                                        rel.n_workers),
            self.globalize_worker_array(np.asarray(rel.valid)[sl],
                                        rel.n_workers),
            rel.vars,
        )

    def barrier(self, tag: str = "barrier") -> None:
        if self.n_processes > 1:
            from repro.compat import host_barrier

            host_barrier(tag)


# ===========================================================================
# Per-shard helpers
# ===========================================================================
def _wrap(body, mesh, axis, in_specs, out_specs):
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _pw(axis) -> PartitionSpec:  # leading worker axis sharded
    return PartitionSpec(axis)


def _pb(axis) -> PartitionSpec:  # replicated batch axis, then worker axis
    return PartitionSpec(None, axis)


_PR = PartitionSpec()  # replicated


def _block_transpose(axis: str, send: jax.Array, k: int) -> jax.Array:
    """The (W_sender, W_receiver) block transpose as a collective.

    ``send``: (*batch_k, W_local, W, ...) — axis k the local sender block,
    axis k+1 the *global* receiver index.  The tiled all_to_all ships each
    receiver block to its owner; the swap restores receiver-major layout, so
    the result is (*batch_k, W_local_receivers, W_global_senders, ...) —
    exactly ``jnp.swapaxes(send, k, k+1)`` of the global view, sharded on
    the receiver axis."""
    out = jax.lax.all_to_all(send, axis, split_axis=k + 1, concat_axis=k,
                             tiled=True)
    return jnp.swapaxes(out, k, k + 1)


def _pmax(x: jax.Array, axis: str) -> jax.Array:
    """``pmax`` of non-negative overflow totals over the mesh axis.

    The TPU compiler lowers only Sum for 64-bit all-reduces, so an int64
    total is reduced as two int32 words: the max of the high words, then
    the max of the low words among the shards that hold it.  Exact, and
    still one all-reduce family on the compiled HLO."""
    if x.dtype != jnp.int64:
        return jax.lax.pmax(x, axis)
    hi = (x >> 31).astype(jnp.int32)
    lo = (x & 0x7FFFFFFF).astype(jnp.int32)
    top = jax.lax.pmax(hi, axis)
    low = jax.lax.pmax(jnp.where(hi == top, lo, -1), axis)
    return (top.astype(jnp.int64) << 31) | low.astype(jnp.int64)


def _global_worker_ids(axis: str, w_local: int) -> jax.Array:
    """Global worker index of each local worker on this shard."""
    d = jax.lax.axis_index(axis)
    return d * w_local + jnp.arange(w_local)


def _offdiag_cells(axis: str, svalid: jax.Array) -> jax.Array:
    """Off-diagonal (actually-on-the-wire) cell count of a local send
    buffer (W_local, W, cap): worker w -> w traffic stays local."""
    w_local = svalid.shape[0]
    gids = _global_worker_ids(axis, w_local)
    diag = jnp.sum(svalid[jnp.arange(w_local), gids])
    return jax.lax.psum(jnp.sum(svalid) - diag, axis)


def _offdiag_cells_batch(axis: str, svalid: jax.Array) -> jax.Array:
    """Batched form over (B, W_local, W, cap): per-query (B,) counts."""
    w_local = svalid.shape[1]
    gids = _global_worker_ids(axis, w_local)
    diag = jnp.sum(svalid[:, jnp.arange(w_local), gids], axis=(1, 2))
    return jax.lax.psum(jnp.sum(svalid, axis=(1, 2, 3)) - diag, axis)


# ===========================================================================
# Sharded stage wrappers (module-level jit: one shared compile cache)
# ===========================================================================
@partial(jax.jit, static_argnames=("mesh", "axis", "use_po", "nid", "backend"))
def _match_ranges_sharded(mesh, axis, store, p_const, sk_const, use_po, nid,
                          backend):
    def body(store, p_const, sk_const):
        return match_ranges(store, p_const, sk_const, use_po=use_po, nid=nid,
                            backend=backend)

    return _wrap(body, mesh, axis, (_pw(axis), _PR, _PR),
                 (_pw(axis), _pw(axis)))(store, p_const, sk_const)


@partial(jax.jit, static_argnames=("mesh", "axis", "spec", "cap_out",
                                   "backend"))
def _match_rows_sharded(mesh, axis, store, consts, spec, cap_out, backend):
    def body(store, consts):
        rows, valid, total = dsj.match_rows(store, consts, spec, cap_out,
                                            backend=backend)
        return rows, valid, _pmax(total, axis)

    return _wrap(body, mesh, axis, (_pw(axis), _PR),
                 (_pw(axis), _pw(axis), _PR))(store, consts)


@partial(jax.jit, static_argnames=("mesh", "axis", "spec", "cap_out",
                                   "backend"))
def _match_first_sharded(mesh, axis, store, consts, spec, cap_out, backend):
    def body(store, consts):
        cols, valid, total = dsj.match_first(store, consts, spec, cap_out,
                                             backend=backend)
        return cols, valid, _pmax(total, axis)

    return _wrap(body, mesh, axis, (_pw(axis), _PR),
                 (_pw(axis), _pw(axis), _PR))(store, consts)


@partial(jax.jit, static_argnames=("mesh", "axis", "col_idx", "cap_proj",
                                   "backend"))
def _project_unique_sharded(mesh, axis, cols, valid, col_idx, cap_proj,
                            backend):
    def body(cols, valid):
        proj, pvalid, n = dsj.project_unique(cols, valid, col_idx, cap_proj,
                                             backend=backend)
        return proj, pvalid, _pmax(n, axis)

    return _wrap(body, mesh, axis, (_pw(axis), _pw(axis)),
                 (_pw(axis), _pw(axis), _PR))(cols, valid)


@partial(jax.jit, static_argnames=("mesh", "axis", "cap_peer", "backend",
                                   "pspec"))
def _exchange_hash_sharded(mesh, axis, proj, proj_valid, cap_peer, backend,
                           pspec=None, table=None):
    w_global = proj.shape[0]

    # Placement exception table (directory policies): a *replicated* operand
    # of the shard_map body — every shard reads the same table, and table
    # growth within a capacity class is just new operand values, no retrace.
    # The hash path (pspec None) does not thread the table at all, so its
    # traced body and jit cache keys are exactly the historical ones.
    if pspec is None:

        def body(proj, proj_valid):
            send, svalid, maxw = dsj.hash_send_buffers(
                proj, proj_valid, w_global, cap_peer, backend
            )
            recv = _block_transpose(axis, send, 0)
            recv_valid = _block_transpose(axis, svalid, 0)
            cells = _offdiag_cells(axis, svalid)
            maxb = _pmax(jnp.max(maxw), axis)
            return recv, recv_valid, cells.astype(jnp.int64), maxb

        return _wrap(body, mesh, axis, (_pw(axis), _pw(axis)),
                     (_pw(axis), _pw(axis), _PR, _PR))(proj, proj_valid)

    def body(proj, proj_valid, table):
        send, svalid, maxw = dsj.hash_send_buffers(
            proj, proj_valid, w_global, cap_peer, backend,
            spec=pspec, table=table,
        )
        recv = _block_transpose(axis, send, 0)
        recv_valid = _block_transpose(axis, svalid, 0)
        cells = _offdiag_cells(axis, svalid)
        maxb = _pmax(jnp.max(maxw), axis)
        return recv, recv_valid, cells.astype(jnp.int64), maxb

    return _wrap(body, mesh, axis, (_pw(axis), _pw(axis), _PR),
                 (_pw(axis), _pw(axis), _PR, _PR))(proj, proj_valid, table)


@partial(jax.jit, static_argnames=("mesh", "axis"))
def _exchange_broadcast_sharded(mesh, axis, proj, proj_valid):
    w_global = proj.shape[0]

    def body(proj, proj_valid):
        full = jax.lax.all_gather(proj, axis, axis=0, tiled=True)
        fullv = jax.lax.all_gather(proj_valid, axis, axis=0, tiled=True)
        w_local = proj.shape[0]
        recv = jnp.broadcast_to(full[None], (w_local,) + full.shape)
        recv_valid = jnp.broadcast_to(fullv[None], (w_local,) + fullv.shape)
        cells = jax.lax.psum(jnp.sum(proj_valid), axis) * (w_global - 1)
        return recv, recv_valid, cells.astype(jnp.int64)

    return _wrap(body, mesh, axis, (_pw(axis), _pw(axis)),
                 (_pw(axis), _pw(axis), _PR))(proj, proj_valid)


@partial(jax.jit, static_argnames=("mesh", "axis", "spec", "probe_col",
                                   "cap_flat", "cap_cand", "backend"))
def _probe_and_reply_sharded(mesh, axis, store, recv, recv_valid, consts,
                             spec, probe_col, cap_flat, cap_cand, backend):
    def body(store, recv, recv_valid, consts):
        send, svalid, totals, maxb = dsj.reply_send_buffers(
            store, recv, recv_valid, consts, spec, probe_col, cap_flat,
            cap_cand, backend,
        )
        cand = _block_transpose(axis, send, 0)
        cand_valid = _block_transpose(axis, svalid, 0)
        cells = _offdiag_cells(axis, svalid) * 3
        return (
            cand,
            cand_valid,
            cells.astype(jnp.int64),
            _pmax(jnp.max(totals), axis),
            _pmax(jnp.max(maxb), axis),
        )

    return _wrap(body, mesh, axis, (_pw(axis), _pw(axis), _pw(axis), _PR),
                 (_pw(axis), _pw(axis), _PR, _PR, _PR))(
        store, recv, recv_valid, consts
    )


@partial(jax.jit, static_argnames=("mesh", "axis", "join_col_rel",
                                   "probe_col", "shared_checks",
                                   "append_cols", "cap_out", "backend"))
def _finalize_join_sharded(mesh, axis, rel_cols, rel_valid, cand, cand_valid,
                           join_col_rel, probe_col, shared_checks,
                           append_cols, cap_out, backend):
    def body(rel_cols, rel_valid, cand, cand_valid):
        cols, valid, total = dsj.finalize_join(
            rel_cols, rel_valid, cand, cand_valid, join_col_rel, probe_col,
            shared_checks, append_cols, cap_out, backend=backend,
        )
        return cols, valid, _pmax(total, axis)

    return _wrap(body, mesh, axis,
                 (_pw(axis), _pw(axis), _pw(axis), _pw(axis)),
                 (_pw(axis), _pw(axis), _PR))(
        rel_cols, rel_valid, cand, cand_valid
    )


@partial(jax.jit, static_argnames=("mesh", "axis", "spec", "join_col_rel",
                                   "probe_col", "shared_checks",
                                   "append_cols", "cap_out", "backend"))
def _local_probe_join_sharded(mesh, axis, store, rel_cols, rel_valid, consts,
                              spec, join_col_rel, probe_col, shared_checks,
                              append_cols, cap_out, backend):
    def body(store, rel_cols, rel_valid, consts):
        cols, valid, total = dsj.local_probe_join(
            store, rel_cols, rel_valid, consts, spec, join_col_rel,
            probe_col, shared_checks, append_cols, cap_out, backend=backend,
        )
        return cols, valid, _pmax(total, axis)

    return _wrap(body, mesh, axis, (_pw(axis), _pw(axis), _pw(axis), _PR),
                 (_pw(axis), _pw(axis), _PR))(
        store, rel_cols, rel_valid, consts
    )


# --------------------------------------------- shard-local stage wrappers
# The parallel-mode stages without their total-pmax: every op in the body
# is per-worker local, inputs are either P(axis)-sharded or replicated, and
# the per-shard overflow totals leave as a P(axis)-sharded (D,) vector —
# nothing forces XLA to emit a collective, and the zero-collective test
# asserts none appears.  The host reduces the totals during the overflow
# check (``host_total``), a sync it performs anyway.
@partial(jax.jit, static_argnames=("mesh", "axis", "spec", "cap_out",
                                   "backend"))
def _match_first_shardlocal(mesh, axis, store, consts, spec, cap_out,
                            backend):
    def body(store, consts):
        cols, valid, total = dsj.match_first(store, consts, spec, cap_out,
                                             backend=backend)
        return cols, valid, total[None]

    return _wrap(body, mesh, axis, (_pw(axis), _PR),
                 (_pw(axis), _pw(axis), _pw(axis)))(store, consts)


@partial(jax.jit, static_argnames=("mesh", "axis", "spec", "join_col_rel",
                                   "probe_col", "shared_checks",
                                   "append_cols", "cap_out", "backend"))
def _local_probe_join_shardlocal(mesh, axis, store, rel_cols, rel_valid,
                                 consts, spec, join_col_rel, probe_col,
                                 shared_checks, append_cols, cap_out,
                                 backend):
    def body(store, rel_cols, rel_valid, consts):
        cols, valid, total = dsj.local_probe_join(
            store, rel_cols, rel_valid, consts, spec, join_col_rel,
            probe_col, shared_checks, append_cols, cap_out, backend=backend,
        )
        return cols, valid, total[None]

    return _wrap(body, mesh, axis, (_pw(axis), _pw(axis), _pw(axis), _PR),
                 (_pw(axis), _pw(axis), _pw(axis)))(
        store, rel_cols, rel_valid, consts
    )


# ------------------------------------------- fused chain wrappers (§11)
# The whole case-(i) query — match_first plus every local join — as ONE
# shard_map body with zero cross-shard collectives: every stage is
# per-worker local and the per-stage per-shard overflow totals leave as a
# P('data')-sharded stage-major matrix ((S, D), batched (S, B, D)) for the
# host's single end-of-chain sync.  The *_from variants are the speculative
# retry's suffix restart, seeded from the last accepted intermediate.
@partial(jax.jit, static_argnames=("mesh", "axis", "first_spec", "first_keep",
                                   "steps", "caps", "backend"))
def _local_chain_shardlocal(mesh, axis, store, consts, first_spec, first_keep,
                            steps, caps, backend):
    def body(store, consts):
        rels, totals = dsj.local_chain(store, consts, first_spec, first_keep,
                                       steps, caps, backend=backend)
        return rels, totals[:, None]

    n_stages = 1 + len(steps)
    rel_specs = tuple((_pw(axis), _pw(axis)) for _ in range(n_stages))
    return _wrap(body, mesh, axis, (_pw(axis), _PR),
                 (rel_specs, _pb(axis)))(store, consts)


@partial(jax.jit, static_argnames=("mesh", "axis", "steps", "caps", "backend"))
def _local_chain_from_shardlocal(mesh, axis, store, rel_cols, rel_valid,
                                 consts, steps, caps, backend):
    def body(store, rel_cols, rel_valid, consts):
        rels, totals = dsj.local_chain_from(store, rel_cols, rel_valid,
                                            consts, steps, caps,
                                            backend=backend)
        return rels, totals[:, None]

    rel_specs = tuple((_pw(axis), _pw(axis)) for _ in steps)
    return _wrap(body, mesh, axis, (_pw(axis), _pw(axis), _pw(axis), _PR),
                 (rel_specs, _pb(axis)))(store, rel_cols, rel_valid, consts)


@partial(jax.jit, static_argnames=("mesh", "axis", "first_spec", "first_keep",
                                   "steps", "caps", "backend"))
def _local_chain_batch_shardlocal(mesh, axis, store, consts, first_spec,
                                  first_keep, steps, caps, backend):
    def body(store, consts):
        rels, totals = dsj.local_chain_batch(store, consts, first_spec,
                                             first_keep, steps, caps,
                                             backend=backend)
        return rels, totals[:, :, None]

    n_stages = 1 + len(steps)
    rel_specs = tuple((_pb(axis), _pb(axis)) for _ in range(n_stages))
    totals_spec = PartitionSpec(None, None, axis)
    return _wrap(body, mesh, axis, (_pw(axis), _PR),
                 (rel_specs, totals_spec))(store, consts)


@partial(jax.jit, static_argnames=("mesh", "axis", "steps", "caps", "backend"))
def _local_chain_from_batch_shardlocal(mesh, axis, store, rel_cols, rel_valid,
                                       consts, steps, caps, backend):
    def body(store, rel_cols, rel_valid, consts):
        rels, totals = dsj.local_chain_from_batch(store, rel_cols, rel_valid,
                                                  consts, steps, caps,
                                                  backend=backend)
        return rels, totals[:, :, None]

    rel_specs = tuple((_pb(axis), _pb(axis)) for _ in steps)
    totals_spec = PartitionSpec(None, None, axis)
    return _wrap(body, mesh, axis, (_pw(axis), _pb(axis), _pb(axis), _PR),
                 (rel_specs, totals_spec))(store, rel_cols, rel_valid, consts)


# ------------------------------------------------------- batched variants
@partial(jax.jit, static_argnames=("mesh", "axis", "spec", "cap_out",
                                   "backend"))
def _match_first_batch_sharded(mesh, axis, store, consts, spec, cap_out,
                               backend):
    def body(store, consts):
        cols, valid, totals = dsj.match_first_batch(store, consts, spec,
                                                    cap_out, backend=backend)
        return cols, valid, _pmax(totals, axis)

    return _wrap(body, mesh, axis, (_pw(axis), _PR),
                 (_pb(axis), _pb(axis), _PR))(store, consts)


@partial(jax.jit, static_argnames=("mesh", "axis", "col_idx", "cap_proj",
                                   "backend"))
def _project_unique_batch_sharded(mesh, axis, cols, valid, col_idx, cap_proj,
                                  backend):
    def body(cols, valid):
        proj, pvalid, n = dsj.project_unique_batch(
            cols, valid, col_idx, cap_proj, backend=backend
        )
        return proj, pvalid, _pmax(n, axis)

    return _wrap(body, mesh, axis, (_pb(axis), _pb(axis)),
                 (_pb(axis), _pb(axis), _PR))(cols, valid)


@partial(jax.jit, static_argnames=("mesh", "axis", "cap_peer", "backend",
                                   "pspec"))
def _exchange_hash_batch_sharded(mesh, axis, proj, proj_valid, cap_peer,
                                 backend, pspec=None, table=None):
    w_global = proj.shape[1]

    # See _exchange_hash_sharded: the exception table is a replicated body
    # operand on the directory path and absent on the hash path.
    if pspec is None:

        def body(proj, proj_valid):  # (B, W_local, cap_proj)
            send, svalid, maxw = jax.vmap(
                lambda p, v: dsj.hash_send_buffers(p, v, w_global, cap_peer,
                                                   backend)
            )(proj, proj_valid)
            recv = _block_transpose(axis, send, 1)
            recv_valid = _block_transpose(axis, svalid, 1)
            cells = _offdiag_cells_batch(axis, svalid)
            maxb = _pmax(jnp.max(maxw, axis=1), axis)
            return recv, recv_valid, cells.astype(jnp.int64), maxb

        return _wrap(body, mesh, axis, (_pb(axis), _pb(axis)),
                     (_pb(axis), _pb(axis), _PR, _PR))(proj, proj_valid)

    def body(proj, proj_valid, table):  # (B, W_local, cap_proj)
        send, svalid, maxw = jax.vmap(
            lambda p, v: dsj.hash_send_buffers(p, v, w_global, cap_peer,
                                               backend, spec=pspec,
                                               table=table)
        )(proj, proj_valid)
        recv = _block_transpose(axis, send, 1)
        recv_valid = _block_transpose(axis, svalid, 1)
        cells = _offdiag_cells_batch(axis, svalid)
        maxb = _pmax(jnp.max(maxw, axis=1), axis)
        return recv, recv_valid, cells.astype(jnp.int64), maxb

    return _wrap(body, mesh, axis, (_pb(axis), _pb(axis), _PR),
                 (_pb(axis), _pb(axis), _PR, _PR))(proj, proj_valid, table)


@partial(jax.jit, static_argnames=("mesh", "axis"))
def _exchange_broadcast_batch_sharded(mesh, axis, proj, proj_valid):
    w_global = proj.shape[1]

    def body(proj, proj_valid):  # (B, W_local, cap_proj)
        full = jax.lax.all_gather(proj, axis, axis=1, tiled=True)
        fullv = jax.lax.all_gather(proj_valid, axis, axis=1, tiled=True)
        w_local = proj.shape[1]
        recv = jnp.broadcast_to(full[:, None], (full.shape[0], w_local)
                                + full.shape[1:])
        recv_valid = jnp.broadcast_to(fullv[:, None],
                                      (fullv.shape[0], w_local)
                                      + fullv.shape[1:])
        cells = jax.lax.psum(jnp.sum(proj_valid, axis=(1, 2)), axis) * (
            w_global - 1
        )
        return recv, recv_valid, cells.astype(jnp.int64)

    return _wrap(body, mesh, axis, (_pb(axis), _pb(axis)),
                 (_pb(axis), _pb(axis), _PR))(proj, proj_valid)


@partial(jax.jit, static_argnames=("mesh", "axis", "spec", "probe_col",
                                   "cap_flat", "cap_cand", "backend"))
def _probe_and_reply_batch_sharded(mesh, axis, store, recv, recv_valid,
                                   consts, spec, probe_col, cap_flat,
                                   cap_cand, backend):
    def body(store, recv, recv_valid, consts):
        send, svalid, totals, maxb = jax.vmap(
            lambda r, rv, c: dsj.reply_send_buffers(
                store, r, rv, c, spec, probe_col, cap_flat, cap_cand, backend
            )
        )(recv, recv_valid, consts)
        cand = _block_transpose(axis, send, 1)
        cand_valid = _block_transpose(axis, svalid, 1)
        cells = _offdiag_cells_batch(axis, svalid) * 3
        return (
            cand,
            cand_valid,
            cells.astype(jnp.int64),
            _pmax(jnp.max(totals, axis=1), axis),
            _pmax(jnp.max(maxb, axis=1), axis),
        )

    return _wrap(body, mesh, axis, (_pw(axis), _pb(axis), _pb(axis), _PR),
                 (_pb(axis), _pb(axis), _PR, _PR, _PR))(
        store, recv, recv_valid, consts
    )


@partial(jax.jit, static_argnames=("mesh", "axis", "join_col_rel",
                                   "probe_col", "shared_checks",
                                   "append_cols", "cap_out", "backend"))
def _finalize_join_batch_sharded(mesh, axis, rel_cols, rel_valid, cand,
                                 cand_valid, join_col_rel, probe_col,
                                 shared_checks, append_cols, cap_out,
                                 backend):
    def body(rel_cols, rel_valid, cand, cand_valid):
        cols, valid, totals = dsj.finalize_join_batch(
            rel_cols, rel_valid, cand, cand_valid, join_col_rel, probe_col,
            shared_checks, append_cols, cap_out, backend=backend,
        )
        return cols, valid, _pmax(totals, axis)

    return _wrap(body, mesh, axis,
                 (_pb(axis), _pb(axis), _pb(axis), _pb(axis)),
                 (_pb(axis), _pb(axis), _PR))(
        rel_cols, rel_valid, cand, cand_valid
    )


@partial(jax.jit, static_argnames=("mesh", "axis", "spec", "join_col_rel",
                                   "probe_col", "shared_checks",
                                   "append_cols", "cap_out", "backend"))
def _local_probe_join_batch_sharded(mesh, axis, store, rel_cols, rel_valid,
                                    consts, spec, join_col_rel, probe_col,
                                    shared_checks, append_cols, cap_out,
                                    backend):
    def body(store, rel_cols, rel_valid, consts):
        cols, valid, totals = dsj.local_probe_join_batch(
            store, rel_cols, rel_valid, consts, spec, join_col_rel,
            probe_col, shared_checks, append_cols, cap_out, backend=backend,
        )
        return cols, valid, _pmax(totals, axis)

    return _wrap(body, mesh, axis, (_pw(axis), _pb(axis), _pb(axis), _PR),
                 (_pb(axis), _pb(axis), _PR))(
        store, rel_cols, rel_valid, consts
    )


# Every sharded stage entry point, for backend.probe_compile_cache_size —
# the recompile regressions hold the sharded path to the same zero-growth
# standard as the single-device stages.
SHARDED_STAGE_FNS = (
    _match_ranges_sharded,
    _match_rows_sharded,
    _match_first_sharded,
    _project_unique_sharded,
    _exchange_hash_sharded,
    _exchange_broadcast_sharded,
    _probe_and_reply_sharded,
    _finalize_join_sharded,
    _local_probe_join_sharded,
    _match_first_batch_sharded,
    _project_unique_batch_sharded,
    _exchange_hash_batch_sharded,
    _exchange_broadcast_batch_sharded,
    _probe_and_reply_batch_sharded,
    _finalize_join_batch_sharded,
    _local_probe_join_batch_sharded,
    _match_first_shardlocal,
    _local_probe_join_shardlocal,
    _local_chain_shardlocal,
    _local_chain_from_shardlocal,
    _local_chain_batch_shardlocal,
    _local_chain_from_batch_shardlocal,
)
