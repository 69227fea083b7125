"""Pallas kernel for ``relalg.unique_compact`` — fused sort-dedupe-compact.

The reference does argsort + gather + adjacent-dedupe + cumsum-scatter (two
data-dependent permutations).  The kernel keeps the whole array in VMEM as
lane-dense (rows, 128) int32 — int64 values as two order-preserving int32
words compared lexicographically (``common.int64_words``) — and fuses the
pipeline gather-free:

  1. key invalid slots to the pad sentinel,
  2. bitonic sort (statically unrolled compare-exchange network; each stage
     fetches every element's partner ``i ^ j`` with two rotations — along
     the lanes for j < 128, along the rows otherwise — then min/max/select),
  3. mask duplicates against the rotated predecessor,
  4. re-key masked slots to the sentinel and bitonic-sort again: because
     survivors are already in order, the second sort is exactly the stable
     compaction of the unique values to a prefix.

Sentinel discipline (same contract as the reference): valid values must be
strictly below ``pad`` — the engine's I32MAX pad guarantees it.  The array
must fit in VMEM and the unrolled network grows as log^2 of its length
(tests/test_tpu_compile.py compiles it for v5e at 2^14 rows).
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
    compiler_params,
    default_interpret,
    int64_words,
)

__all__ = ["unique_compact_pallas"]


def _padded_len(n: int) -> int:
    """Power of two >= n, at least one int32 tile."""
    return max(1 << max(n - 1, 1).bit_length(), TILE)


def _partner(x: jax.Array, j: int) -> jax.Array:
    """x[i ^ j] for every flat index i (j a power of two)."""
    rows = x.shape[0]
    if j < LANES:
        axis, size, step = 1, LANES, j
        bit = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) & j
    else:
        axis, size, step = 0, rows, j // LANES
        bit = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) & step
    up = _roll(x, size - step, axis)  # x[i + j]
    down = _roll(x, step, axis)  # x[i - j]
    return jnp.where(bit == 0, up, down)


def _roll(x: jax.Array, shift: int, axis: int) -> jax.Array:
    """``jnp.roll`` on the TPU's rotate unit (int32 shift under x64)."""
    return pltpu.roll(x, jnp.int32(shift), axis)


def _less(a: tuple, b: tuple) -> jax.Array:
    """Lexicographic a < b over tuples of int32 words."""
    if len(a) == 1:
        return a[0] < b[0]
    return (a[0] < b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))


def _bitonic_sort(x: tuple) -> tuple:
    """Ascending bitonic sort of (rows, 128) word blocks, rows a power of
    two; ``x`` holds one block per word, most significant first."""
    shape = x[0].shape
    n = x[0].size
    flat = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            other = tuple(_partner(w, j) for w in x)
            # the lower index of a pair keeps the min in an ascending run
            keep_min = ((flat & j) == 0) == ((flat & k) == 0)
            keep_self = keep_min == _less(x, other)
            x = tuple(jnp.where(keep_self, w, o) for w, o in zip(x, other))
            j //= 2
        k *= 2
    return x


def _kernel(*refs, pad: tuple[int, ...]):
    n_words = len(pad)
    valid = refs[n_words][...] != 0
    uniq_refs, n_ref = refs[n_words + 1:-1], refs[-1]
    big = tuple(jnp.int32(p) for p in pad)
    x = _bitonic_sort(tuple(jnp.where(valid, r[...], b)
                            for r, b in zip(refs[:n_words], big)))
    shape = x[0].shape
    first_lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1) == 0
    first_row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) == 0
    new = first_lane & first_row
    live = jnp.zeros(shape, jnp.bool_)
    for w, b in zip(x, big):
        prev = _roll(w, 1, 1)  # w[i - 1] within the row
        prev = jnp.where(first_lane, _roll(prev, 1, 0), prev)
        new = new | (w != prev)
        live = live | (w != b)
    mask = new & live
    # one axis at a time: Mosaic lowers a joint reduction through int64
    per_row = jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True,
                      dtype=jnp.int32)
    n_ref[...] = jnp.broadcast_to(
        jnp.sum(per_row, axis=0, keepdims=True, dtype=jnp.int32), n_ref.shape
    )
    # second sort of the re-keyed array == stable compaction to a prefix
    out = _bitonic_sort(tuple(jnp.where(mask, w, b) for w, b in zip(x, big)))
    for r, w in zip(uniq_refs, out):
        r[...] = w


def _pad_words(pad: int, dtype) -> tuple[int, ...]:
    if dtype == jnp.int32:
        return (pad,)
    return (pad >> 32, (pad & 0xFFFFFFFF) - (1 << 31))


def unique_compact_pallas(
    values: jax.Array,  # (n,) int32 or int64
    valid: jax.Array,  # (n,)
    out_cap: int,
    pad: jax.Array | int,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused unique_compact: (uniq (out_cap,), mask, n_unique int64) — same
    contract as the reference (pad must exceed every valid value)."""
    if interpret is None:
        interpret = default_interpret()
    pad = int(pad)
    n_pad = _padded_len(values.shape[0])
    words = int64_words(values)
    pad_words = _pad_words(pad, values.dtype)
    rows = jax.ShapeDtypeStruct((n_pad // LANES, LANES), jnp.int32)
    # whole-array blocks with int32 index maps (the defaults return Python
    # ints, which become int64 block indices under vmap with x64 on)
    whole = pl.BlockSpec((n_pad // LANES, LANES),
                         lambda: (jnp.int32(0), jnp.int32(0)))
    count = pl.BlockSpec((8, LANES), lambda: (jnp.int32(0), jnp.int32(0)))
    *uniq, n_unique = pl.pallas_call(
        functools.partial(_kernel, pad=pad_words),
        in_specs=[whole] * (len(words) + 1),
        out_specs=[whole] * len(words) + [count],
        out_shape=[rows] * len(words)
        + [jax.ShapeDtypeStruct((8, LANES), jnp.int32)],
        compiler_params=compiler_params(interpret, ()),
        interpret=interpret,
    )(*(as_rows(w, n_pad, p) for w, p in zip(words, pad_words)),
      as_rows(valid.astype(jnp.int32), n_pad, 0))
    if len(uniq) == 1:
        uniq = uniq[0].reshape(-1)
    else:  # reassemble int64 from (high, biased low) words
        hi, lo = (w.reshape(-1).astype(jnp.int64) for w in uniq)
        uniq = (hi << 32) + (lo + (1 << 31))
    if out_cap <= n_pad:
        uniq = uniq[:out_cap]
    else:
        uniq = jnp.pad(uniq, (0, out_cap - n_pad), constant_values=pad)
    n64 = n_unique[0, 0].astype(jnp.int64)
    uvalid = jnp.arange(out_cap, dtype=jnp.int64) < n64
    return uniq, uvalid, n64
