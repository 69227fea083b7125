"""Host-side helpers shared across the tree (no side effects on import).

Two definitions live here:

``shard_map``
    Both the LM model stack (``repro.models``) and the RDF execution
    substrate (``repro.core.substrate``) wrap per-shard bodies in
    ``jax.shard_map``; this module is the single place that fixes its
    flags, so the two layers can never drift.

``fetch_global`` / ``host_barrier``
    The one way any host-side code materializes a device array, and the
    one way processes rendezvous.  Under a single process ``fetch_global``
    is ``np.asarray``; under a multi-process mesh (``jax.distributed``) a
    worker-axis-sharded array is *not fully addressable* — each process
    only holds its own device shards — so the local shards are exchanged
    through the **coordination-service key-value store** (gRPC) and
    reassembled by shard index.  Deliberately *not* a gloo collective:
    host-side fetches interleave with the data plane's in-program
    collectives, and on oversubscribed CPU (CI runners, 1-core boxes) that
    interleaving can desync gloo's TCP pairs — observed as
    ``op.preamble.length <= op.nbytes`` aborts, silently corrupted
    allgather payloads, and both-process hangs inside
    ``process_allgather``.  The coordination service is a separate,
    acknowledged transport, so control traffic can never cross wires with
    data-plane collectives.  All processes run the same host control flow
    in lockstep (the substrate's SPMD contract), so the per-process fetch
    sequence numbers — which form the KV keys — always agree.

Kept outside ``repro.core`` on purpose: importing ``repro.core`` enables
jax x64 globally, which the model stack must not inherit.
"""
from __future__ import annotations

import base64
import pickle

import jax
import numpy as np

__all__ = ["shard_map", "fetch_global", "host_barrier"]

# generous: on oversubscribed CPU a peer may sit behind a minutes-long XLA
# compile before reaching the matching fetch/barrier; the launcher (or
# cluster manager) timeout is the real backstop
_TIMEOUT_MS = 600_000
# stay well under gRPC's default 4 MiB message ceiling (base64 already
# inflates payloads by 4/3)
_KV_CHUNK = 1_500_000

_fetch_seq = 0
_barrier_seq: dict[str, int] = {}


def _coordination_client():
    """The jax.distributed coordination-service client, or None when the
    process never joined a multi-process mesh."""
    from jax._src import distributed

    return distributed.global_state.client


def _kv_fetch_global(x, client) -> np.ndarray:
    """Assemble ``x``'s global value by exchanging local shard blocks
    through the coordination-service KV store.

    Every process publishes its addressable shards (deduplicated by shard
    index — replica copies carry no extra information), reads every other
    process's blocks, and scatters them into the global shape by index.
    Lockstep call counts give identical ``seq`` on all processes, so the
    keys pair up; the trailing barrier lets each process delete its own
    keys without racing a slow reader."""
    global _fetch_seq
    seq = _fetch_seq
    _fetch_seq += 1
    pid = jax.process_index()
    blocks: dict[tuple, np.ndarray] = {}
    for sh in x.addressable_shards:
        key = tuple((s.start, s.stop) for s in sh.index)
        if key not in blocks:
            blocks[key] = np.asarray(sh.data)
    enc = base64.b64encode(
        pickle.dumps(list(blocks.items()), protocol=pickle.HIGHEST_PROTOCOL)
    ).decode("ascii")
    chunks = [enc[i:i + _KV_CHUNK] for i in range(0, len(enc), _KV_CHUNK)]
    chunks = chunks or [""]
    prefix = f"fg/{seq}/{pid}"
    client.key_value_set(f"{prefix}/n", str(len(chunks)))
    for j, c in enumerate(chunks):
        client.key_value_set(f"{prefix}/{j}", c)

    out = np.zeros(x.shape, dtype=x.dtype)
    filled = np.zeros(x.shape, dtype=bool)
    for p in range(jax.process_count()):
        if p == pid:
            items = list(blocks.items())
        else:
            pp = f"fg/{seq}/{p}"
            n = int(client.blocking_key_value_get(f"{pp}/n", _TIMEOUT_MS))
            payload = "".join(
                client.blocking_key_value_get(f"{pp}/{j}", _TIMEOUT_MS)
                for j in range(n)
            )
            items = pickle.loads(base64.b64decode(payload))
        for key, arr in items:
            idx = tuple(slice(a, b) for a, b in key)
            out[idx] = arr
            filled[idx] = True
    if not filled.all():
        raise RuntimeError(
            f"fetch_global seq={seq}: shard blocks from "
            f"{jax.process_count()} processes left the global array "
            f"incompletely covered (shape {x.shape})"
        )
    client.wait_at_barrier(f"fg/{seq}", _TIMEOUT_MS)
    client.key_value_delete(f"{prefix}/n")
    for j in range(len(chunks)):
        client.key_value_delete(f"{prefix}/{j}")
    return out


def fetch_global(x) -> np.ndarray:
    """Materialize ``x`` on the host with its *global* shape.

    numpy inputs and fully-addressable jax arrays take the plain
    ``np.asarray`` path (identical to the historical behavior, including
    under the single-process mesh).  Non-fully-addressable arrays — worker
    shards spanning processes — are reassembled from per-process shard
    blocks exchanged over the coordination service (see module docstring
    for why this is not a gloo allgather)."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        client = _coordination_client()
        if client is None:
            raise RuntimeError(
                "array spans processes but this process never joined "
                "jax.distributed"
            )
        return _kv_fetch_global(x, client)
    return np.asarray(x)


def host_barrier(tag: str = "barrier", timeout_ms: int = _TIMEOUT_MS) -> None:
    """Block until every process reaches this barrier.

    Coordination-service barrier (one-shot ids, so a per-tag lockstep
    counter makes each use unique); no-op under a single process."""
    if jax.process_count() <= 1:
        return
    client = _coordination_client()
    seq = _barrier_seq.get(tag, 0)
    _barrier_seq[tag] = seq + 1
    client.wait_at_barrier(f"hb/{tag}/{seq}", timeout_ms)


def shard_map(fn, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the project's default (no VMA checking)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
