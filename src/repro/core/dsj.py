"""Distributed Semi-Join data plane (paper §4.1, Algorithm 1 internals).

Every stage is a pure, jitted global-view function over arrays with a leading
worker axis W.  Executors never call these directly — dispatch goes through
the execution substrate (``repro.core.substrate``): the single-device default
runs them as-is, while ``MeshSubstrate`` wraps them in ``shard_map`` with W
sharded on the mesh ``data`` axis, where

  * the (W_sender, W_receiver) block transpose in ``exchange_hash`` / the
    ``probe_and_reply`` reply route becomes an **all_to_all** (the paper's
    hash distribution / point-to-point candidate shipping),
  * the sender-axis broadcast in ``exchange_broadcast`` becomes an
    **all_gather** (the paper's projection-column broadcast)

— asserted on compiled HLO in tests/test_substrate_mesh.py.  The choice
between the two is exactly Observation 1 and is made by the locality-aware
planner.  Each stage also returns the number of int32 cells it put on the
wire, which the engine aggregates into the per-query communication
accounting used by the paper's experiments (Figs. 11b, 13b, 14b).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial, wraps

import jax
import jax.numpy as jnp

from .backend import get_impl, range_search, register_impl
from .placement import splitmix64_jnp
from .query import O, P, S, TriplePattern, Var
from .relalg import bucket_by_dest, expand, unique_compact
from .relation import Relation
from .triples import ShardedTripleStore, gather_rows, match_ranges, probe_values

__all__ = [
    "PatternSpec",
    "ChainStep",
    "jnp_hash_ids",
    "match_first",
    "project_unique",
    "hash_send_buffers",
    "exchange_hash",
    "exchange_broadcast",
    "reply_send_buffers",
    "probe_and_reply",
    "finalize_join",
    "local_probe_join",
    "local_chain",
    "local_chain_from",
    "match_first_batch",
    "project_unique_batch",
    "exchange_hash_batch",
    "exchange_broadcast_batch",
    "probe_and_reply_batch",
    "finalize_join_batch",
    "local_probe_join_batch",
    "local_chain_batch",
    "local_chain_from_batch",
]

I32MAX = jnp.iinfo(jnp.int32).max


def _scope(name: str):
    """Run a stage's body under ``jax.named_scope(name)``, so the device
    trace can attribute its operations; the jitted name is unchanged."""

    def wrap(fn):
        @wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return scoped

    return wrap


# splitmix64 finalizer — bit-identical to ``partition.hash_ids``; historical
# spelling of the canonical ``placement.splitmix64_jnp``.
jnp_hash_ids = splitmix64_jnp


# ---------------------------------------------------------------------------
# Host-static description of a triple pattern (structure only, no id values).
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class PatternSpec:
    s_const: bool
    p_const: bool
    o_const: bool
    same_var_so: bool  # pattern like (?x, p, ?x)
    var_cols: tuple[int, ...]  # columns (S/P/O) carrying the pattern's vars

    @classmethod
    def of(cls, q: TriplePattern) -> "PatternSpec":
        return cls(
            s_const=not isinstance(q.s, Var),
            p_const=not isinstance(q.p, Var),
            o_const=not isinstance(q.o, Var),
            same_var_so=isinstance(q.s, Var) and q.s == q.o,
            var_cols=tuple(c for _, c in q.var_cols()),
        )


@dataclass(frozen=True)
class ChainStep:
    """Host-static description of one case-(i) local join in a fused chain.

    Mirrors the argument block of ``local_probe_join`` (c2 is always the
    pinned subject for main-index chains, but the probe column is kept
    explicit so replica-index chains could reuse the machinery)."""

    spec: PatternSpec
    join_col_rel: int  # c1: column of the running relation carrying join var
    probe_col: int  # c2: triple column the values bind (S in case (i))
    shared_checks: tuple[tuple[int, int], ...]
    append_cols: tuple[int, ...]


def pattern_consts(q: TriplePattern) -> jnp.ndarray:
    """(3,) int32: constant id per column, -1 where variable."""
    vals = [t.id if not isinstance(t, Var) else -1 for t in (q.s, q.p, q.o)]
    return jnp.asarray(vals, dtype=jnp.int32)


def _residual_mask(rows: jax.Array, valid: jax.Array, spec: PatternSpec,
                   consts: jax.Array, probed: tuple[int, ...]) -> jax.Array:
    """Enforce pattern constants not already enforced by the index probe,
    plus same-variable (?x p ?x) equality."""
    for c, is_c in ((S, spec.s_const), (P, spec.p_const), (O, spec.o_const)):
        if is_c and c not in probed:
            valid = valid & (rows[..., c] == consts[c])
    if spec.same_var_so:
        valid = valid & (rows[..., S] == rows[..., O])
    return valid


# ---------------------------------------------------------------- first match
@partial(jax.jit, static_argnames=("spec", "cap_out", "backend"))
def match_rows(
    store: ShardedTripleStore,
    consts: jax.Array,  # (3,) int32, -1 = variable
    spec: PatternSpec,
    cap_out: int,
    backend: str = "searchsorted",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Local pattern match returning full triple rows (used by IRD).

    Returns (rows (W, cap_out, 3), valid, max_total)."""
    with jax.named_scope("probe"):
        if spec.p_const and spec.s_const:
            use_po, probed = False, (P, S)
            lo, hi = match_ranges(store, consts[P], consts[S], use_po=False,
                                  nid=store.n_ids, backend=backend)
        elif spec.p_const and spec.o_const:
            use_po, probed = True, (P, O)
            lo, hi = match_ranges(store, consts[P], consts[O], use_po=True,
                                  nid=store.n_ids, backend=backend)
        elif spec.p_const:
            use_po, probed = False, (P,)
            lo, hi = match_ranges(store, consts[P], jnp.int32(-1),
                                  use_po=False, nid=store.n_ids,
                                  backend=backend)
        else:
            use_po, probed = False, ()
            lo, hi = match_ranges(store, jnp.int32(-1), jnp.int32(-1),
                                  use_po=False, nid=store.n_ids,
                                  backend=backend)
    with jax.named_scope("expand"):
        rows, _, valid, totals = gather_rows(
            store, lo[:, None], hi[:, None], cap_out, use_po=use_po,
            backend=backend,
        )
    valid = _residual_mask(rows, valid, spec, consts, probed)
    return rows, valid, jnp.max(totals)


@partial(jax.jit, static_argnames=("spec", "cap_out", "backend"))
def match_first(
    store: ShardedTripleStore,
    consts: jax.Array,  # (3,) int32, -1 = variable
    spec: PatternSpec,
    cap_out: int,
    backend: str = "searchsorted",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """answerSubquery(q) on local shards (Algorithm 1 line 10).

    Returns (cols (W, cap_out, k), valid (W, cap_out), max_total (scalar)).
    Index selection mirrors §3.2: (p,s)->PS, (p,o)->PO, (p)->P, else scan.
    """
    rows, valid, max_total = match_rows(store, consts, spec, cap_out,
                                        backend=backend)
    cols = rows[..., list(spec.var_cols)] if spec.var_cols else rows[..., :0]
    cols = jnp.where(valid[..., None], cols, -1)
    return cols, valid, max_total


# ----------------------------------------------------------------- projection
@partial(jax.jit, static_argnames=("col_idx", "cap_proj", "backend"))
def project_unique(
    cols: jax.Array, valid: jax.Array, col_idx: int, cap_proj: int,
    backend: str = "searchsorted",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """pi_c(RS) with per-worker dedup (the paper ships projected columns).

    Returns (proj (W, cap_proj), proj_valid, max_unique (overflow check))."""

    def per_worker(c_w, v_w):
        with jax.named_scope("unique_compact"):
            u, uv, n = unique_compact(c_w[:, col_idx], v_w, cap_proj, I32MAX,
                                      backend=backend)
        return jnp.where(uv, u, -1), uv, n

    proj, pvalid, n = jax.vmap(per_worker)(cols, valid)
    return proj, pvalid, jnp.max(n)


# ------------------------------------------------------------------ exchanges
def hash_send_buffers(
    proj: jax.Array,  # (W_block, cap_proj) — all workers, or one mesh shard
    proj_valid: jax.Array,
    n_workers: int,  # global worker count (the hash modulus)
    cap_peer: int,
    backend: str = "searchsorted",
    spec=None,  # placement.PlacementSpec | None (None = plain hash owner)
    table=None,  # placement.DirectoryTable operand when spec is directory
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-worker destination bucketing for the hash exchange.

    Shared by ``exchange_hash`` (whole worker axis) and the mesh substrate
    (local worker block, global destinations) — one definition, so the two
    paths cannot drift.  With a directory placement spec, each value fans
    out to the whole split set of its subject (replication factor is the
    static ``spec.max_split``; excess replicas are invalid entries), since a
    split subject's triples live on several shards and every one must be
    probed.  Returns (send (W_block, n_workers, cap_peer), send_valid,
    max_wanted (W_block,))."""

    def per_worker(p_w, v_w):
        if spec is None:
            dest = (jnp_hash_ids(p_w) % n_workers).astype(jnp.int32)
            with jax.named_scope("bucket_by_dest"):
                send, svalid, max_wanted = bucket_by_dest(
                    p_w[:, None], dest, v_w, n_workers, cap_peer, backend=backend
                )
            return send[..., 0], svalid, max_wanted
        dests, dvalid = spec.value_dests(p_w, v_w, table)  # (F, n) each
        vals = jnp.broadcast_to(p_w[None], dests.shape).reshape(-1)
        with jax.named_scope("bucket_by_dest"):
            send, svalid, max_wanted = bucket_by_dest(
                vals[:, None], dests.reshape(-1), dvalid.reshape(-1),
                n_workers, cap_peer, backend=backend
            )
        return send[..., 0], svalid, max_wanted

    return jax.vmap(per_worker)(proj, proj_valid)


@partial(jax.jit, static_argnames=("cap_peer", "backend", "spec"))
@_scope("exchange_hash")
def exchange_hash(
    proj: jax.Array,  # (W, cap_proj)
    proj_valid: jax.Array,
    cap_peer: int,
    backend: str = "searchsorted",
    spec=None,
    table=None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Observation 1 fast path: hash-distribute the projected join column.

    The placement policy names the owner(s) of each value: under the default
    hash placement (``spec=None``) that is H(v) mod W and each value goes to
    exactly one worker; under directory placement a split subject's value is
    replicated to its whole split set (see ``hash_send_buffers``).  The
    (sender, receiver) transpose lowers to all_to_all under sharding.
    Returns (recv (W_recv, W_send, cap_peer), recv_valid, cells_sent,
    max_bucket)."""
    w = proj.shape[0]
    send, svalid, maxw = hash_send_buffers(proj, proj_valid, w, cap_peer,
                                           backend, spec=spec, table=table)
    # (W_sender, W_receiver, cap) -> (W_receiver, W_sender, cap): all_to_all
    recv = jnp.swapaxes(send, 0, 1)
    recv_valid = jnp.swapaxes(svalid, 0, 1)
    # off-diagonal traffic only (w -> w stays local)
    diag = jnp.sum(svalid[jnp.arange(w), jnp.arange(w)])
    cells = jnp.sum(svalid) - diag
    return recv, recv_valid, cells.astype(jnp.int64), jnp.max(maxw)


@jax.jit
def exchange_broadcast(
    proj: jax.Array, proj_valid: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Observation 1 slow path: every worker receives every projection.

    The sender-axis broadcast lowers to all_gather under sharding.
    Returns (recv (W_recv, W_send, cap_proj), recv_valid, cells_sent)."""
    w = proj.shape[0]
    recv = jnp.broadcast_to(proj[None], (w,) + proj.shape)
    recv_valid = jnp.broadcast_to(proj_valid[None], (w,) + proj_valid.shape)
    cells = jnp.sum(proj_valid) * (w - 1)  # each value shipped to W-1 peers
    return recv, recv_valid, cells.astype(jnp.int64)


# -------------------------------------------------------------- probe + reply
def reply_send_buffers(
    store: ShardedTripleStore,
    recv: jax.Array,  # (W_block, n_send, cap_peer) — whole axis or one shard
    recv_valid: jax.Array,
    consts: jax.Array,
    spec: PatternSpec,
    probe_col: int,
    cap_flat: int,
    cap_cand: int,
    backend: str = "searchsorted",
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Local semi-join probe + per-sender candidate bucketing — everything
    ``probe_and_reply`` does before the reply-route transpose.  Shared with
    the mesh substrate (local worker block, global senders) so the probe
    semantics cannot drift between the two paths.

    Returns (send (W_block, n_send, cap_cand, 3), send_valid,
    totals (W_block,), max_bucket (W_block,))."""
    w_block, n_send, cap_peer = recv.shape
    flat_vals = recv.reshape(w_block, n_send * cap_peer)
    flat_valid = recv_valid.reshape(w_block, n_send * cap_peer)
    with jax.named_scope("probe"):
        lo, hi = probe_values(
            store, consts[P], flat_vals, flat_valid, col=probe_col,
            nid=store.n_ids, backend=backend,
        )
    with jax.named_scope("expand"):
        rows, src, valid, totals = gather_rows(
            store, lo, hi, cap_flat, use_po=(probe_col == O), backend=backend
        )
    valid = _residual_mask(rows, valid, spec, consts, probed=(P, probe_col))
    sender = src // cap_peer  # which sender's value produced this row

    def per_worker(rows_w, sender_w, valid_w):
        with jax.named_scope("bucket_by_dest"):
            return bucket_by_dest(rows_w, sender_w, valid_w, n_send, cap_cand,
                                  backend=backend)

    send, svalid, maxb = jax.vmap(per_worker)(rows, sender, valid)
    return send, svalid, totals, maxb


@partial(jax.jit, static_argnames=("spec", "probe_col", "cap_flat", "cap_cand",
                                   "backend"))
@_scope("probe_and_reply")
def probe_and_reply(
    store: ShardedTripleStore,
    recv: jax.Array,  # (W, W_send, cap_peer) received join-column values
    recv_valid: jax.Array,
    consts: jax.Array,  # (3,) pattern constants
    spec: PatternSpec,
    probe_col: int,  # S, P or O — the column the values bind (c2)
    cap_flat: int,  # probe expansion capacity (this worker, all senders)
    cap_cand: int,  # per-(replier, sender) candidate capacity
    backend: str = "searchsorted",
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Each worker semi-joins the received values against its local index and
    routes candidate triples back to their senders (Algorithm 1 lines 13-23).

    Returns (cand (W_sender, W_replier, cap_cand, 3), cand_valid, cells_sent,
    max_flat, max_bucket) — cand is already routed back (transposed)."""
    w = recv.shape[0]
    send, svalid, totals, maxb = reply_send_buffers(
        store, recv, recv_valid, consts, spec, probe_col, cap_flat, cap_cand,
        backend,
    )
    # (W_replier, W_sender, cap, 3) -> (W_sender, W_replier, cap, 3)
    cand = jnp.swapaxes(send, 0, 1)
    cand_valid = jnp.swapaxes(svalid, 0, 1)
    diag = jnp.sum(svalid[jnp.arange(w), jnp.arange(w)])
    cells = (jnp.sum(svalid) - diag) * 3
    return cand, cand_valid, cells.astype(jnp.int64), jnp.max(totals), jnp.max(maxb)


# ------------------------------------------------------------------- finalize
@partial(jax.jit, static_argnames=("join_col_rel", "probe_col",
                                   "shared_checks", "append_cols", "cap_out",
                                   "backend"))
@_scope("finalize_join")
def finalize_join(
    rel_cols: jax.Array,  # (W, capR, k) current intermediate RS1
    rel_valid: jax.Array,
    cand: jax.Array,  # (W, R, cap_cand, 3) candidate triples (routed back)
    cand_valid: jax.Array,
    join_col_rel: int,  # column of RS1 carrying the join variable (c1)
    probe_col: int,  # column of the candidate triple carrying c2
    # (rel_col, triple_col) equality checks for additional shared variables
    shared_checks: tuple[tuple[int, int], ...],
    append_cols: tuple[int, ...],  # triple columns to append (new variables)
    cap_out: int,
    backend: str = "searchsorted",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """RS1 |><| candidates on RS1.c1 = cand.c2 (local hash join, line 27).

    New columns appended for the pattern's variables *not* already bound.
    Returns (out_cols (W, cap_out, k + new), out_valid, max_total)."""
    w, r, cc, _ = cand.shape
    flat_cand = cand.reshape(w, r * cc, 3)
    flat_cvalid = cand_valid.reshape(w, r * cc)

    def per_worker(rcols, rvalid, cnd, cvalid):
        key = jnp.where(cvalid, cnd[:, probe_col], I32MAX)
        order = jnp.argsort(key)
        skey = key[order]
        scand = cnd[order]
        probe = jnp.where(rvalid, rcols[:, join_col_rel], I32MAX)
        with jax.named_scope("probe"):
            lo, hi = range_search(skey, probe, backend=backend)
        hi = jnp.where(rvalid & (probe != I32MAX), hi, lo)
        with jax.named_scope("expand"):
            left, pos, valid, total = expand(lo, hi, cap_out, backend=backend)
        ltuple = rcols[left]
        rtriple = scand[jnp.minimum(pos, scand.shape[0] - 1)]
        for rc, tc in shared_checks:
            valid = valid & (ltuple[:, rc] == rtriple[:, tc])
        new_cols = [rtriple[:, c] for c in append_cols]
        out = (
            jnp.concatenate([ltuple] + [c[:, None] for c in new_cols], axis=1)
            if new_cols
            else ltuple
        )
        out = jnp.where(valid[:, None], out, -1)
        return out, valid, total

    out_cols, out_valid, totals = jax.vmap(per_worker)(
        rel_cols, rel_valid, flat_cand, flat_cvalid
    )
    return out_cols, out_valid, jnp.max(totals)


# ----------------------------------------------------- case (i): no-comm join
@partial(jax.jit, static_argnames=("spec", "join_col_rel", "probe_col",
                                   "shared_checks", "append_cols", "cap_out",
                                   "backend"))
def local_probe_join(
    store: ShardedTripleStore,
    rel_cols: jax.Array,  # (W, capR, k)
    rel_valid: jax.Array,
    consts: jax.Array,
    spec: PatternSpec,
    join_col_rel: int,
    probe_col: int,  # S in case (i); any col for replica-index local joins
    shared_checks: tuple[tuple[int, int], ...],
    append_cols: tuple[int, ...],
    cap_out: int,
    backend: str = "searchsorted",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """JoinWithoutCommunication (Algorithm 1 line 7): c2 = pinned subject, so
    every matching triple is already local.  Probe own index directly."""
    vals = rel_cols[:, :, join_col_rel]
    with jax.named_scope("probe"):
        lo, hi = probe_values(
            store, consts[P], vals, rel_valid, col=probe_col, nid=store.n_ids,
            backend=backend,
        )
    with jax.named_scope("expand"):
        rows, src, valid, totals = gather_rows(
            store, lo, hi, cap_out, use_po=(probe_col == O), backend=backend
        )
    valid = _residual_mask(rows, valid, spec, consts, probed=(P, probe_col))

    def per_worker(rcols, rows_w, src_w, valid_w):
        ltuple = rcols[src_w]
        v = valid_w
        for rc, tc in shared_checks:
            v = v & (ltuple[:, rc] == rows_w[:, tc])
        new_cols = [rows_w[:, c] for c in append_cols]
        out = (
            jnp.concatenate([ltuple] + [c[:, None] for c in new_cols], axis=1)
            if new_cols
            else ltuple
        )
        out = jnp.where(v[:, None], out, -1)
        return out, v

    out_cols, out_valid = jax.vmap(per_worker)(rel_cols, rows, src, valid)
    return out_cols, out_valid, jnp.max(totals)


# ===================================================== batched (multi-query)
# vmap-lifted variants of the stages above: one dispatch evaluates a whole
# shape bucket of queries stacked on a leading batch axis B.  All queries in
# a bucket share the static arguments (PatternSpec, capacities, join
# structure — that is what WorkloadBatcher buckets on); only the pattern
# constants and the flowing arrays differ per query.  The store is broadcast
# (in_axes=None): every query probes the same immutable shards.  Per-query
# scalars (comm cells, overflow totals) come back as (B,) arrays so the
# executor keeps the paper's per-query communication accounting exact.


@partial(jax.jit, static_argnames=("spec", "cap_out", "backend"))
def match_first_batch(
    store: ShardedTripleStore,
    consts: jax.Array,  # (B, 3) int32, -1 = variable
    spec: PatternSpec,
    cap_out: int,
    backend: str = "searchsorted",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched ``match_first``: (cols (B, W, cap_out, k), valid, total (B,))."""
    fn = partial(match_first, spec=spec, cap_out=cap_out, backend=backend)
    return jax.vmap(fn, in_axes=(None, 0))(store, consts)


@partial(jax.jit, static_argnames=("col_idx", "cap_proj", "backend"))
def project_unique_batch(
    cols: jax.Array,  # (B, W, capR, k)
    valid: jax.Array,
    col_idx: int,
    cap_proj: int,
    backend: str = "searchsorted",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched ``project_unique``: (proj (B, W, cap_proj), valid, max (B,))."""
    fn = partial(project_unique, col_idx=col_idx, cap_proj=cap_proj,
                 backend=backend)
    return jax.vmap(fn)(cols, valid)


@partial(jax.jit, static_argnames=("cap_peer", "backend", "spec"))
def exchange_hash_batch(
    proj: jax.Array,  # (B, W, cap_proj)
    proj_valid: jax.Array,
    cap_peer: int,
    backend: str = "searchsorted",
    spec=None,
    table=None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Batched ``exchange_hash``; cells (B,) is per-query wire accounting.

    The placement exception table (if any) is closed over, i.e. broadcast
    across the batch axis rather than vmapped."""
    fn = lambda p, v: exchange_hash(p, v, cap_peer=cap_peer, backend=backend,
                                    spec=spec, table=table)
    return jax.vmap(fn)(proj, proj_valid)


@jax.jit
def exchange_broadcast_batch(
    proj: jax.Array, proj_valid: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched ``exchange_broadcast``; cells (B,) per query."""
    return jax.vmap(exchange_broadcast)(proj, proj_valid)


@partial(jax.jit, static_argnames=("spec", "probe_col", "cap_flat", "cap_cand",
                                   "backend"))
def probe_and_reply_batch(
    store: ShardedTripleStore,
    recv: jax.Array,  # (B, W, W_send, cap_peer)
    recv_valid: jax.Array,
    consts: jax.Array,  # (B, 3)
    spec: PatternSpec,
    probe_col: int,
    cap_flat: int,
    cap_cand: int,
    backend: str = "searchsorted",
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Batched ``probe_and_reply``; cells/max_flat/max_bucket are (B,)."""
    fn = partial(probe_and_reply, spec=spec, probe_col=probe_col,
                 cap_flat=cap_flat, cap_cand=cap_cand, backend=backend)
    return jax.vmap(fn, in_axes=(None, 0, 0, 0))(
        store, recv, recv_valid, consts
    )


@partial(jax.jit, static_argnames=("join_col_rel", "probe_col",
                                   "shared_checks", "append_cols", "cap_out",
                                   "backend"))
def finalize_join_batch(
    rel_cols: jax.Array,  # (B, W, capR, k)
    rel_valid: jax.Array,
    cand: jax.Array,  # (B, W, R, cap_cand, 3)
    cand_valid: jax.Array,
    join_col_rel: int,
    probe_col: int,
    shared_checks: tuple[tuple[int, int], ...],
    append_cols: tuple[int, ...],
    cap_out: int,
    backend: str = "searchsorted",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched ``finalize_join``: (out (B, W, cap_out, k+new), valid, (B,))."""
    fn = partial(finalize_join, join_col_rel=join_col_rel,
                 probe_col=probe_col, shared_checks=shared_checks,
                 append_cols=append_cols, cap_out=cap_out, backend=backend)
    return jax.vmap(fn)(rel_cols, rel_valid, cand, cand_valid)


@partial(jax.jit, static_argnames=("spec", "join_col_rel", "probe_col",
                                   "shared_checks", "append_cols", "cap_out",
                                   "backend"))
def local_probe_join_batch(
    store: ShardedTripleStore,
    rel_cols: jax.Array,  # (B, W, capR, k)
    rel_valid: jax.Array,
    consts: jax.Array,  # (B, 3)
    spec: PatternSpec,
    join_col_rel: int,
    probe_col: int,
    shared_checks: tuple[tuple[int, int], ...],
    append_cols: tuple[int, ...],
    cap_out: int,
    backend: str = "searchsorted",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched ``local_probe_join`` (store broadcast, queries batched)."""
    fn = partial(local_probe_join, spec=spec, join_col_rel=join_col_rel,
                 probe_col=probe_col, shared_checks=shared_checks,
                 append_cols=append_cols, cap_out=cap_out, backend=backend)
    return jax.vmap(fn, in_axes=(None, 0, 0, 0))(
        store, rel_cols, rel_valid, consts
    )


# ================================================ fused case-(i) chain bodies
# When *every* join of a query is case (i) (subject-star under a
# local-join-safe placement — the paper's Observation (i)), the whole query
# is one communication-free per-shard program: match_first followed by N
# local probe joins.  The bodies below fuse that program into a single
# traceable function so the substrate can stage the entire chain as ONE
# dispatch (one shard_map body on the mesh) instead of one per stage, and so
# the executor can defer every overflow check to a single stacked totals
# vector fetched once at chain end (the speculative one-sync retry protocol,
# DESIGN.md §11).
#
# The bodies dispatch through the data-plane registry
# (``get_impl("local_chain*", backend)``) like every other hot primitive:
# the reference composition below simply chains the stage functions (so
# answers are bit-identical to the per-stage path by construction), while a
# Pallas provider may re-register a fused per-shard grid pass.


@_scope("local_chain")
def _local_chain_body(store, consts, first_spec, first_keep, steps, caps,
                      backend):
    """match_first + N local probe joins; returns (per-stage rels, totals).

    ``consts`` is (1+N, 3); ``caps[i]`` is stage i's capacity class.
    ``first_keep`` drops duplicate-variable columns after the first match
    (the c1 indices in ``steps`` assume the post-keep layout, exactly as the
    sequential executor and BatchPlan do).  Per-stage intermediates are all
    returned because the speculative retry restarts from the last accepted
    stage.  totals is (1+N,) stacked stage-major."""
    cols, valid, t0 = match_first(store, consts[0], first_spec, caps[0],
                                  backend=backend)
    if len(first_keep) != len(first_spec.var_cols):
        cols = cols[..., list(first_keep)]
    rels = [(cols, valid)]
    totals = [t0]
    for i, stp in enumerate(steps):
        cols, valid, t = local_probe_join(
            store, cols, valid, consts[1 + i], stp.spec, stp.join_col_rel,
            stp.probe_col, stp.shared_checks, stp.append_cols, caps[1 + i],
            backend=backend,
        )
        rels.append((cols, valid))
        totals.append(t)
    return tuple(rels), jnp.stack(totals)


@_scope("local_chain")
def _local_chain_from_body(store, rel_cols, rel_valid, consts, steps, caps,
                           backend):
    """Suffix restart: re-run ``steps`` seeded from an accepted intermediate.

    ``consts`` is (N_tail, 3) aligned with ``steps``/``caps`` (row i feeds
    step i).  Used by the retry protocol to re-run only the overflowed
    suffix of a chain."""
    cols, valid = rel_cols, rel_valid
    rels = []
    totals = []
    for i, stp in enumerate(steps):
        cols, valid, t = local_probe_join(
            store, cols, valid, consts[i], stp.spec, stp.join_col_rel,
            stp.probe_col, stp.shared_checks, stp.append_cols, caps[i],
            backend=backend,
        )
        rels.append((cols, valid))
        totals.append(t)
    return tuple(rels), jnp.stack(totals)


register_impl("local_chain", "searchsorted")(_local_chain_body)
register_impl("local_chain_from", "searchsorted")(_local_chain_from_body)


@partial(jax.jit, static_argnames=("first_spec", "first_keep", "steps",
                                   "caps", "backend"))
def local_chain(
    store: ShardedTripleStore,
    consts: jax.Array,  # (1+N, 3) int32, row 0 = first pattern
    first_spec: PatternSpec,
    first_keep: tuple[int, ...],
    steps: tuple[ChainStep, ...],
    caps: tuple[int, ...],  # (1+N,) per-stage capacity classes
    backend: str = "searchsorted",
) -> tuple[tuple[tuple[jax.Array, jax.Array], ...], jax.Array]:
    """Whole case-(i) query in one dispatch.

    Returns (rels, totals) where rels[i] = (cols (W, caps[i], k_i), valid)
    for stage i (0 = post-match_first) and totals is the (1+N,) stacked
    per-stage overflow vector — the executor's single host sync."""
    return get_impl("local_chain", backend)(
        store, consts, first_spec, first_keep, steps, caps, backend
    )


@partial(jax.jit, static_argnames=("steps", "caps", "backend"))
def local_chain_from(
    store: ShardedTripleStore,
    rel_cols: jax.Array,  # (W, capR, k) accepted intermediate
    rel_valid: jax.Array,
    consts: jax.Array,  # (N_tail, 3) aligned with steps
    steps: tuple[ChainStep, ...],
    caps: tuple[int, ...],
    backend: str = "searchsorted",
) -> tuple[tuple[tuple[jax.Array, jax.Array], ...], jax.Array]:
    """Retry entry point: run a chain suffix from a saved intermediate."""
    return get_impl("local_chain_from", backend)(
        store, rel_cols, rel_valid, consts, steps, caps, backend
    )


@partial(jax.jit, static_argnames=("first_spec", "first_keep", "steps",
                                   "caps", "backend"))
def local_chain_batch(
    store: ShardedTripleStore,
    consts: jax.Array,  # (B, 1+N, 3)
    first_spec: PatternSpec,
    first_keep: tuple[int, ...],
    steps: tuple[ChainStep, ...],
    caps: tuple[int, ...],
    backend: str = "searchsorted",
) -> tuple[tuple[tuple[jax.Array, jax.Array], ...], jax.Array]:
    """Batched fused chain: one dispatch for a whole shape bucket.

    rels[i] leaves gain a leading B axis; totals comes back (1+N, B)
    stage-major so the executor can take per-stage maxima without a
    transpose on the host."""
    body = get_impl("local_chain", backend)
    fn = lambda c: body(store, c, first_spec, first_keep, steps, caps,
                        backend)
    rels, totals = jax.vmap(fn)(consts)
    return rels, jnp.swapaxes(totals, 0, 1)


@partial(jax.jit, static_argnames=("steps", "caps", "backend"))
def local_chain_from_batch(
    store: ShardedTripleStore,
    rel_cols: jax.Array,  # (B, W, capR, k)
    rel_valid: jax.Array,
    consts: jax.Array,  # (B, N_tail, 3)
    steps: tuple[ChainStep, ...],
    caps: tuple[int, ...],
    backend: str = "searchsorted",
) -> tuple[tuple[tuple[jax.Array, jax.Array], ...], jax.Array]:
    """Batched suffix restart; totals (N_tail, B) stage-major."""
    body = get_impl("local_chain_from", backend)
    fn = lambda rc, rv, c: body(store, rc, rv, c, steps, caps, backend)
    rels, totals = jax.vmap(fn)(rel_cols, rel_valid, consts)
    return rels, jnp.swapaxes(totals, 0, 1)
