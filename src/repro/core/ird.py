"""Incremental ReDistribution — IRD (paper §5.3, Algorithm 3).

Given a hot pattern's redistribution tree, the data it touches is re-hashed
around the bindings of the core vertex, level by level:

  Phase 1 — first-hop edges: triples adjacent to the core are hash
  distributed on the core binding.  If the core is the triple's *subject*
  nothing moves (footnote 7: the initial subject-hash partitioning already
  placed them) and the edge is served by the main index.

  Phase 2 — deeper edges: triples are collocated with their parent-edge
  triples through a series of distributed semi-joins (the same machinery as
  query evaluation): each worker projects the *propagating column* of its
  parent-edge triples, the projection is exchanged (hash when the child
  edge's source column is a subject, Observation 1 again; broadcast
  otherwise), candidate triples are routed back and indexed in the per-edge
  replica module.

Replicas are maintained as raw triples in segregated storage modules so the
normal index machinery (and eviction) applies — paper §5.5.

The DSJ stages run through the execution substrate, so under a mesh
substrate IRD's own exchanges lower to the same collectives as query
evaluation; freshly built replica modules are re-placed on the substrate
(``shard_store``) before they serve parallel-mode queries.

**Overlapped (deferred) mode.**  ``redistribute_deferred`` dispatches the
same phase-1/phase-2 work but does not wait for it: JAX async dispatch means
every exchange collective and the replica-module indexing sort are merely
*enqueued* when the call returns, and the host is free to evaluate the next
shape bucket of the query stream while they execute.  The returned
:class:`PendingRedistribution` keeps the device-derived accounting
(wire-cell counts, indexed-triple counts) as unconverted device scalars —
converting them early would force the very sync the mode exists to avoid —
and ``finalize()`` is the barrier: it blocks until every freshly built
replica buffer is materialized, then folds the counters into
:class:`IRDStats`.  The engine finalizes *before* publishing the pattern
index, so adaptivity state stays sequential-equivalent: a query can only be
routed to a replica module that is already consistent.  The replica indexing
itself is one fused jitted dispatch whose staging buffers are donated on
platforms with buffer donation (TPU/GPU), letting XLA reuse the exchange
staging memory for the sorted indexes.

The only remaining synchronous points are the overflow-retry capacity
checks (host control flow by design) — the expensive tail (final exchanges,
sort-indexing, accounting reductions) all lands behind the barrier.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from . import dsj
from .backend import quantize_capacity
from .heatmap import HotPattern
from .pattern_index import ReplicaIndex
from .query import O, S, TriplePattern, Var
from .substrate import host_total
from .transform import RTree, TreeEdge, TreeNode
from .triples import ShardedTripleStore

__all__ = ["IRDStats", "IncrementalRedistributor", "PendingRedistribution",
           "PendingRebalance"]

_MAX_RETRIES = 7


# --------------------------------------------------------- replica indexing
# One fused dispatch for the sort-indexing of a freshly exchanged replica
# module (ShardedTripleStore.from_device_rows traced under jit), so the
# whole build is enqueued asynchronously behind the exchange collectives.
# On TPU/GPU the (rows, valid) staging buffers are donated — they are dead
# after this call, and donation lets XLA write the sorted indexes into the
# staging memory instead of allocating fresh buffers.  CPU has no buffer
# donation, so donating there would only emit warnings.
_INDEX_ROWS_JIT = None


def _index_replica_rows(rows: jax.Array, valid: jax.Array, n_ids: int
                        ) -> ShardedTripleStore:
    global _INDEX_ROWS_JIT
    if _INDEX_ROWS_JIT is None:
        donate = (0, 1) if jax.default_backend() in ("tpu", "gpu") else ()
        _INDEX_ROWS_JIT = jax.jit(
            ShardedTripleStore.from_device_rows,
            static_argnames=("n_ids",),
            donate_argnums=donate,
        )
    return _INDEX_ROWS_JIT(rows, valid, n_ids=n_ids)


@dataclass
class IRDStats:
    comm_cells: int = 0
    triples_indexed: int = 0  # data touched by the IRD process (Fig. 16a)
    n_edges: int = 0

    @property
    def comm_bytes(self) -> int:
        return self.comm_cells * 4


@dataclass
class PendingRedistribution:
    """A dispatched-but-not-yet-published redistribution.

    Device work (exchange collectives, replica sort-indexing) is enqueued;
    the replica modules are already registered in the ReplicaIndex but the
    pattern index must not reference them until :meth:`finalize` has run.
    ``finalize`` is the overlap barrier: it blocks until every staged buffer
    is materialized, then folds the deferred device counters into the stats
    — so the (storage, stats) it returns are bit-identical to what the
    synchronous path would have produced."""

    storage: dict[int, str | None] = field(default_factory=dict)
    stats: IRDStats = field(default_factory=IRDStats)
    # device scalars, converted only at the barrier (int() would sync early)
    _cells: list = field(default_factory=list)
    _triples: list = field(default_factory=list)
    _barrier: list = field(default_factory=list)  # arrays to block on
    _done: bool = False

    def finalize(self) -> tuple[dict[int, str | None], IRDStats]:
        if not self._done:
            jax.block_until_ready(self._barrier)
            self.stats.comm_cells += sum(host_total(c) for c in self._cells)
            self.stats.triples_indexed += sum(host_total(t)
                                              for t in self._triples)
            self._cells.clear()
            self._triples.clear()
            self._barrier.clear()
            self._done = True
        return self.storage, self.stats


class IncrementalRedistributor:
    def __init__(
        self,
        main: ShardedTripleStore,
        replicas: ReplicaIndex,
        n_workers: int,
        capacity: int = 1 << 12,
        probe_backend: str = "auto",
        substrate=None,
        placement=None,
    ):
        from .placement import HashPlacement
        from .substrate import SingleDeviceSubstrate

        self.main = main
        self.replicas = replicas
        self.w = n_workers
        self.cap = quantize_capacity(capacity)
        self.placement = placement if placement is not None else \
            HashPlacement(n_workers)
        self.sub = substrate if substrate is not None else \
            SingleDeviceSubstrate()
        self.backend = self.sub.resolve_backend(probe_backend)

    # ------------------------------------------------------------- top level
    def redistribute(self, hot: HotPattern) -> tuple[dict[int, str | None], IRDStats]:
        """Algorithm 3, synchronous: dispatch and immediately barrier.
        Returns pattern_idx -> storage id (None = served by main index) +
        stats.  ``redistribute(hot)`` == ``redistribute_deferred(hot)
        .finalize()`` by construction — one code path, two sync points."""
        return self.redistribute_deferred(hot).finalize()

    def redistribute_deferred(self, hot: HotPattern) -> PendingRedistribution:
        """Algorithm 3 over every root-to-leaf path (DFS), dispatched
        asynchronously.  Exchange collectives and replica indexing are
        enqueued but not waited on; accounting stays on device.  The caller
        may interleave other device work (e.g. the next shape bucket of the
        query stream), then must ``finalize()`` the returned handle before
        publishing the pattern entries it describes."""
        pending = PendingRedistribution()
        stats = pending.stats
        tree = hot.rtree
        storage = pending.storage
        # replica module holding each edge's triples (None = main index)
        store_of_edge: dict[int, ShardedTripleStore | None] = {}
        # the edge that *leads to* each tree node (object identity)
        edge_into: dict[int, TreeEdge] = {}
        for _, e, _ in tree.iter_edges():
            edge_into[id(e.child)] = e

        for parent, edge, depth in tree.iter_edges():
            idx = edge.pattern_idx
            if idx in storage:  # shared prefix already redistributed
                continue
            q = tree.query.patterns[idx]
            stats.n_edges += 1
            if depth == 0:
                if edge.parent_is_subject and self.placement.local_join_safe:
                    # footnote 7: subject-core edges stay in the main index
                    # (but their matches count as data touched by IRD —
                    # paper §6.4.3 counts "data in the main and replica
                    # indices").  Only sound when the placement guarantees
                    # subject collocation; a directory placement may split a
                    # hot subject's star, so its subject-core edges are
                    # collected into a replica module keyed by the subject
                    # (base owner — no split salt, see
                    # _hash_distribute_core_edge).
                    storage[idx] = None
                    store_of_edge[id(edge)] = None
                    self._count_matches(q, pending)
                else:
                    key_col = S if edge.parent_is_subject else O
                    sid, st = self._hash_distribute_core_edge(
                        q, pending, key_col
                    )
                    storage[idx] = sid
                    store_of_edge[id(edge)] = st
            else:
                pedge = edge_into[id(parent)]
                pstore = store_of_edge[id(pedge)]
                pq = tree.query.patterns[pedge.pattern_idx]
                # propagating column of the parent edge = its child side
                prop_col = O if pedge.parent_is_subject else S
                sid, st = self._collocate_edge(
                    q, edge, pq, pstore, prop_col, pending
                )
                storage[idx] = sid
                store_of_edge[id(edge)] = st
        return pending

    def _count_matches(self, q: TriplePattern,
                       pending: PendingRedistribution) -> None:
        """Main-index matches of a pattern (touched-data accounting).  The
        count itself is deferred to the barrier — only the overflow-retry
        capacity check syncs."""
        spec = dsj.PatternSpec.of(q)
        consts = dsj.pattern_consts(q)
        cap = self.cap
        for _ in range(_MAX_RETRIES):
            _, valid, total = self.sub.match_rows(self.main, consts, spec, cap,
                                             backend=self.backend)
            total = host_total(total)
            if total <= cap:
                break
            cap = quantize_capacity(max(cap * 2, total))
        pending._triples.append(jnp.sum(valid))

    # ----------------------------------------------------------- phase 1
    def _hash_distribute_core_edge(
        self, q: TriplePattern, pending: PendingRedistribution,
        key_col: int = O,
    ) -> tuple[str, ShardedTripleStore]:
        """Hash-distribute triples matching q on the core binding (column
        ``key_col``).

        Destinations come from the placement's *base* owner — deliberately
        without the directory split salt: every edge module of a hot pattern
        must place a given core binding on the *same* worker, or the
        parallel-mode local joins between them would miss rows.  A split
        star therefore concentrates in its replica modules (correctness
        first); the skew win comes from the split main-store path."""
        spec = dsj.PatternSpec.of(q)
        consts = dsj.pattern_consts(q)
        cap = self.cap
        for _ in range(_MAX_RETRIES):
            rows, valid, total = self.sub.match_rows(self.main, consts, spec, cap,
                                                backend=self.backend)
            total = host_total(total)
            if total <= cap:
                break
            cap = quantize_capacity(max(cap * 2, total))
        w = self.w
        pspec = self.placement.stage_spec
        ptable = self.placement.device_table()

        def per_worker(rows_w, valid_w):
            keys = rows_w[:, key_col]
            if pspec is None:
                dest = (dsj.jnp_hash_ids(keys) % w).astype(jnp.int32)
            else:
                dest = pspec.owner_dest(keys, valid_w, ptable)
            from .relalg import bucket_by_dest

            return bucket_by_dest(rows_w, dest, valid_w, w, cap,
                                  backend=self.backend)

        cap_peer = cap
        for _ in range(_MAX_RETRIES):
            send, svalid, maxw = jax.vmap(per_worker)(rows, valid)
            maxw = host_total(maxw)
            if maxw <= cap_peer:
                break
            cap_peer = cap = quantize_capacity(max(cap_peer * 2, maxw))
        recv = jnp.swapaxes(send, 0, 1).reshape(self.w, -1, 3)
        rvalid = jnp.swapaxes(svalid, 0, 1).reshape(self.w, -1)
        diag = jnp.sum(svalid[jnp.arange(w), jnp.arange(w)])
        pending._cells.append((jnp.sum(svalid) - diag) * 3)
        st = self._stage_replica(recv, rvalid, pending)
        sid = self.replicas.new_id()
        self.replicas.put(sid, st)
        return sid, st

    def _stage_replica(self, rows: jax.Array, valid: jax.Array,
                       pending: PendingRedistribution) -> ShardedTripleStore:
        """Enqueue the sort-indexing + substrate placement of a replica
        module; the build completes asynchronously behind the exchange
        collectives, and ``pending`` barriers on its buffers before the PI
        may publish it."""
        st = _index_replica_rows(rows, valid, self.main.n_ids)
        st = self.sub.shard_store(st)
        pending._triples.append(jnp.sum(st.counts))
        pending._barrier.extend(st.tree_flatten()[0])
        return st

    # ----------------------------------------------------------- phase 2
    def _collocate_edge(
        self,
        q: TriplePattern,
        edge: TreeEdge,
        parent_q: TriplePattern,
        parent_store: ShardedTripleStore | None,
        prop_col: int,
        pending: PendingRedistribution,
    ) -> tuple[str, ShardedTripleStore]:
        """Collocate triples matching q with their parent-edge triples
        (a DSJ between the parent replica module and the main index)."""
        pstore = parent_store if parent_store is not None else self.main
        pspec = dsj.PatternSpec.of(parent_q)
        pconsts = dsj.pattern_consts(parent_q)
        cap = self.cap
        for _ in range(_MAX_RETRIES):
            prows, pvalid, total = self.sub.match_rows(pstore, pconsts, pspec, cap,
                                                  backend=self.backend)
            total = host_total(total)
            if total <= cap:
                break
            cap = quantize_capacity(max(cap * 2, total))

        # project + dedupe the propagating column
        cap_proj = cap
        for _ in range(_MAX_RETRIES):
            proj, projv, nuniq = self.sub.project_unique(
                prows, pvalid, prop_col, cap_proj, backend=self.backend
            )
            nuniq = host_total(nuniq)
            if nuniq <= cap_proj:
                break
            cap_proj = quantize_capacity(max(cap_proj * 2, nuniq))

        # source column of the child edge: where the parent vertex binds
        src_col = S if edge.parent_is_subject else O
        if src_col == S:
            cap_peer = cap_proj
            # probes the main index, so split subjects need the placement's
            # replicated destinations (same as query-time case ii)
            plc_spec = self.placement.stage_spec
            plc_table = self.placement.device_table()
            for _ in range(_MAX_RETRIES):
                recv, rvalid, cells, maxb = self.sub.exchange_hash(
                    proj, projv, cap_peer, backend=self.backend,
                    spec=plc_spec, table=plc_table,
                )
                maxb = host_total(maxb)
                if maxb <= cap_peer:
                    break
                cap_peer = quantize_capacity(max(cap_peer * 2, maxb))
            pending._cells.append(cells)
        else:
            recv, rvalid, cells = self.sub.exchange_broadcast(proj, projv)
            pending._cells.append(cells)

        spec = dsj.PatternSpec.of(q)
        consts = dsj.pattern_consts(q)
        cap_flat = cap_cand = self.cap
        for _ in range(_MAX_RETRIES):
            cand, cvalid, cells, maxf, maxc = self.sub.probe_and_reply(
                self.main, recv, rvalid, consts, spec, src_col,
                cap_flat, cap_cand, backend=self.backend,
            )
            maxf, maxc = host_total(maxf), host_total(maxc)
            if maxf <= cap_flat and maxc <= cap_cand:
                break
            if maxf > cap_flat:
                cap_flat = quantize_capacity(max(cap_flat * 2, maxf))
            if maxc > cap_cand:
                cap_cand = quantize_capacity(max(cap_cand * 2, maxc))
        pending._cells.append(cells)

        flat = cand.reshape(self.w, -1, 3)
        flatv = cvalid.reshape(self.w, -1)
        st = self._stage_replica(flat, flatv, pending)
        sid = self.replicas.new_id()
        self.replicas.put(sid, st)
        return sid, st

    # ----------------------------------------------------- main-store moves
    def rebalance_deferred(self, placement) -> "PendingRebalance":
        """Re-place the *main* store under a (new) placement policy,
        asynchronously — the hot-key analogue of ``redistribute_deferred``.

        Every worker buckets its live triples by ``placement.triple_dest``
        (split subjects fan out over their split set, salted by the object),
        the (sender, receiver) transpose ships them, and the receiving
        shards are sort-indexed through the same fused dispatch as replica
        modules.  Nothing here blocks: the caller overlaps query traffic and
        calls ``finalize()`` before publishing the rebuilt store.

        Note the rebuild flows through ``from_device_rows``, which drops
        exact duplicate triples — RDF set semantics, and the main store is
        duplicate-free after bootstrap anyway."""
        main = self.main
        w = self.w
        capT = main.capacity
        rows = main.spo_ps  # (W, capT, 3); first counts[w] rows are live
        valid = jnp.arange(capT)[None, :] < main.counts[:, None]
        pspec = placement.stage_spec
        ptable = placement.device_table()

        from .relalg import bucket_by_dest

        def make_per_worker(cap_peer):
            def per_worker(rows_w, valid_w):
                s = rows_w[:, S]
                o = rows_w[:, O]
                if pspec is None:
                    dest = (dsj.jnp_hash_ids(s) % w).astype(jnp.int32)
                else:
                    dest = pspec.triple_dest(s, o, valid_w, ptable)
                return bucket_by_dest(rows_w, dest, valid_w, w, cap_peer,
                                      backend=self.backend)

            return per_worker

        # start near the balanced shard size; retry-double on skew overflow
        cap_peer = quantize_capacity(
            max(host_total(main.counts) // max(w // 2, 1), 1)
        )
        for _ in range(_MAX_RETRIES):
            send, svalid, maxw = jax.vmap(make_per_worker(cap_peer))(
                rows, valid
            )
            maxw = host_total(maxw)
            if maxw <= cap_peer:
                break
            cap_peer = quantize_capacity(max(cap_peer * 2, maxw))
        else:
            raise RuntimeError("rebalance bucketing exceeded retry budget")

        recv = jnp.swapaxes(send, 0, 1).reshape(w, -1, 3)
        rvalid = jnp.swapaxes(svalid, 0, 1).reshape(w, -1)
        diag = jnp.sum(svalid[jnp.arange(w), jnp.arange(w)])
        pending = PendingRebalance()
        pending._cells.append((jnp.sum(svalid) - diag) * 3)
        st = _index_replica_rows(recv, rvalid, main.n_ids)
        st = self.sub.shard_store(st)
        pending.store = st
        pending._barrier.extend(st.tree_flatten()[0])
        return pending


@dataclass
class PendingRebalance:
    """A dispatched-but-not-yet-published main-store rebalance.

    ``finalize()`` barriers on the rebuilt shards and returns
    (new_store, moved_cells); the engine then republishes the store to every
    component (executor, IRD, parallel executor) atomically on the host."""

    store: ShardedTripleStore | None = None
    _cells: list = field(default_factory=list)
    _barrier: list = field(default_factory=list)
    _done: bool = False
    _moved: int = 0

    def finalize(self) -> tuple[ShardedTripleStore, int]:
        if not self._done:
            jax.block_until_ready(self._barrier)
            self._moved = sum(host_total(c) for c in self._cells)
            self._cells.clear()
            self._barrier.clear()
            self._done = True
        return self.store, self._moved
