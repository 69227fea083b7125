"""Pallas kernel for ``relalg.expand`` — join expansion in one grid pass.

The reference implementation materializes ``cum = cumsum(counts)`` and then
binary-searches it once per output row (``searchsorted`` + two gathers).  On
TPU the gathers are the expensive part, so the kernel replaces them with the
same masked-compare reduction as the semijoin probe kernel.  Output
positions ``t`` sit lane-dense in vregs; the input rows are staged in SMEM
and walked one scalar at a time, carrying the running ``cum`` across the
sequential input-block axis, and every row ``i`` with range
``[cum_{i-1}, cum_i)`` updates all positions at once:

  left[t] += [cum_i <= t]                         (the searchsorted result)
  off[t]   = lo_i - cum_{i-1}  where cum_{i-1} <= t < cum_i   (exactly one i)

so ``right_pos = off + t``.  Internals are int32: valid output lanes satisfy
t < out_cap, a buffer size, so they never wrap; the int64 *total* used for
overflow detection is reduced outside the kernel, exactly like the
int64-safe jnp reference.  Lanes past the total are invalid and hold
unspecified positions, as in the reference.
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

__all__ = ["expand_pallas"]


def _kernel(lo_ref, cnt_ref, left_ref, rp_ref, cum_scr, *, in_rows: int,
            out_rows: int, n_in_blocks: int, last_row: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        left_ref[...] = jnp.zeros_like(left_ref)
        rp_ref[...] = jnp.zeros_like(rp_ref)
        cum_scr[0] = jnp.int32(0)

    shape = (out_rows, LANES)
    t = (pl.program_id(0) * (out_rows * LANES)
         + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
         + jax.lax.broadcasted_iota(jnp.int32, shape, 1))

    def one_row(r, l, acc):
        left, off, prev = acc
        cum = prev + cnt_ref[r, l]
        left = left + (cum <= t).astype(jnp.int32)
        off = jnp.where((prev <= t) & (t < cum), lo_ref[r, l] - prev, off)
        return left, off, cum

    left, off, cum = for_each_scalar(
        in_rows, one_row, (left_ref[...], rp_ref[...], cum_scr[0])
    )
    left_ref[...] = left
    rp_ref[...] = off
    cum_scr[0] = cum

    @pl.when(j == n_in_blocks - 1)
    def _final():
        left_ref[...] = jnp.minimum(left_ref[...], last_row)
        rp_ref[...] = rp_ref[...] + t


def expand_pallas(
    lo: jax.Array,  # (n,) range starts
    hi: jax.Array,  # (n,) range ends
    out_cap: int,
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused expand: returns (left_idx, right_pos, valid, total) like the
    reference; block sizes default to the per-platform table."""
    if interpret is None:
        interpret = default_interpret()
    cfg = block_config("relalg_expand")
    block_m = block_m or cfg["block_m"]
    block_n = block_n or cfg["block_n"]
    check_block("block_m", block_m)
    check_block("block_n", block_n)
    n = lo.shape[0]
    lo32 = lo.astype(jnp.int32)
    counts = jnp.maximum(hi.astype(jnp.int32) - lo32, 0)
    # overflow detection must see the unwrapped total -> int64 outside
    total = jnp.sum(counts.astype(jnp.int64)) if n else jnp.int64(0)

    block_m = min(block_m, round_up(out_cap, TILE))
    n_pad = round_up(n, block_n)
    m_pad = round_up(out_cap, block_m)
    grid = (m_pad // block_m, n_pad // block_n)
    in_rows, out_rows = block_n // LANES, block_m // LANES

    # zero-count padding rows never contribute
    in_spec = pl.BlockSpec((in_rows, LANES), lambda i, j: (j, jnp.int32(0)),
                           memory_space=pltpu.SMEM)
    out_spec = pl.BlockSpec((out_rows, LANES), lambda i, j: (i, jnp.int32(0)))
    left, rp = pl.pallas_call(
        functools.partial(_kernel, in_rows=in_rows, out_rows=out_rows,
                          n_in_blocks=grid[1], last_row=max(n, 1) - 1),
        grid=grid,
        in_specs=[in_spec, in_spec],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((m_pad // LANES, LANES),
                                        jnp.int32)] * 2,
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        compiler_params=compiler_params(interpret, ("parallel", "arbitrary")),
        interpret=interpret,
    )(as_rows(lo32, n_pad, 0), as_rows(counts, n_pad, 0))
    valid = jnp.arange(out_cap, dtype=jnp.int64) < total
    return (left.reshape(-1)[:out_cap], rp.reshape(-1)[:out_cap], valid,
            total)
