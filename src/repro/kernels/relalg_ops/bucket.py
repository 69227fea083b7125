"""Pallas kernel for ``relalg.bucket_by_dest`` — count-then-place layout.

The reference builds per-destination send buffers with a stable argsort by
destination plus ``searchsorted`` slicing: O(n log n) and gather-bound.  The
kernel skips the sort.  For every destination ``d`` (parallel grid axis) it
streams the input blocks (sequential axis) through SMEM and walks the rows
in order, carrying the count of rows already placed for ``d``: a row bound
for ``d`` lands in slot ``count`` and bumps it, so row order within a
destination is the original input order, bit-identical to the
stable-argsort reference.  Rows with rank >= cap_peer are counted but not
placed (dropped, like the reference's clamped slices), and the final count
is the rows ``d`` wanted — the overflow signal.

Each column of the send buffer is a lane-dense block of slots that stays
resident in VMEM across the input axis; a placement rewrites the one
(1, 128) row holding the slot.  VMEM is k * cap_peer int32 per buffer and
the scalar walk is n * n_dest steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    LANES,
    TILE,
    as_rows,
    check_block,
    compiler_params,
    default_interpret,
    for_each_scalar,
    round_up,
)
from repro.kernels.tuning import block_config

__all__ = ["bucket_by_dest_pallas"]


def _kernel(dest_ref, *refs, k: int, in_rows: int, cap_peer: int,
            n_in_blocks: int, pad: int):
    val_refs = refs[:k]
    send_ref, cnt_ref, cnt_scr = refs[k:]
    d = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        send_ref[...] = jnp.full(send_ref.shape, pad, jnp.int32)
        cnt_scr[0] = jnp.int32(0)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def one_row(r, l, count):
        mine = dest_ref[r, l] == d

        @pl.when(mine & (count < cap_peer))
        def _place():
            row = pl.ds(count >> 7, 1)  # count // LANES
            at = lane == (count & (LANES - 1))
            for c in range(k):
                cur = send_ref[0, c, row, :]
                send_ref[0, c, row, :] = jnp.where(at, val_refs[c][r, l], cur)

        return count + mine.astype(jnp.int32)

    cnt_scr[0] = for_each_scalar(in_rows, one_row, cnt_scr[0])

    @pl.when(j == n_in_blocks - 1)
    def _final():
        cnt_ref[...] = jnp.full(cnt_ref.shape, cnt_scr[0], jnp.int32)


def bucket_by_dest_pallas(
    values: jax.Array,  # (n, k) int32 payload rows
    dest: jax.Array,  # (n,) destination per row
    valid: jax.Array,  # (n,)
    n_dest: int,
    cap_peer: int,
    pad: int = -1,
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused bucket_by_dest: (send (n_dest, cap_peer, k), send_valid,
    overflow_total int64) — same contract as the reference."""
    if interpret is None:
        interpret = default_interpret()
    block_n = block_n or block_config("relalg_bucket")["block_n"]
    check_block("block_n", block_n)
    if values.dtype != jnp.int32:
        raise TypeError(f"bucket payload must be int32, got {values.dtype}")
    n, k = values.shape
    n_pad = round_up(n, block_n)
    cap_pad = round_up(cap_peer, TILE)
    cap_rows, in_rows = cap_pad // LANES, block_n // LANES
    grid = (n_dest, n_pad // block_n)

    # invalid rows and padding are bound for no destination
    dest32 = jnp.where(valid, dest, -1).astype(jnp.int32)
    cols = [as_rows(values[:, c], n_pad, 0) for c in range(k)]
    in_spec = pl.BlockSpec((in_rows, LANES), lambda d, j: (j, jnp.int32(0)),
                           memory_space=pltpu.SMEM)
    zero = lambda: jnp.int32(0)  # noqa: E731  (index maps stay int32)
    send, counts = pl.pallas_call(
        functools.partial(_kernel, k=k, in_rows=in_rows, cap_peer=cap_peer,
                          n_in_blocks=grid[1], pad=pad),
        grid=grid,
        in_specs=[in_spec] * (1 + k),
        out_specs=[
            pl.BlockSpec((1, k, cap_rows, LANES),
                         lambda d, j: (d, zero(), zero(), zero())),
            pl.BlockSpec((1, 8, LANES), lambda d, j: (d, zero(), zero())),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_dest, k, cap_rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((n_dest, 8, LANES), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        compiler_params=compiler_params(interpret, ("parallel", "arbitrary")),
        interpret=interpret,
    )(as_rows(dest32, n_pad, -1), *cols)
    send = jnp.swapaxes(send.reshape(n_dest, k, cap_pad)[:, :, :cap_peer],
                        1, 2)
    counts = counts[:, 0, 0]
    slot = jnp.arange(cap_peer, dtype=jnp.int32)
    send_valid = slot[None, :] < counts[:, None]
    max_wanted = (
        jnp.max(counts) if n_dest else jnp.int32(0)
    ).astype(jnp.int64)
    return send, send_valid, max_wanted
