"""Pallas TPU kernel for the DSJ semi-join probe (paper §4.1 hot loop).

Given a worker's sorted composite-key index (p * NID + s|o, padded with the
dtype maximum) and a block of probe keys (the received join-column values),
the kernel computes each probe's match range [lo, hi) — a vectorized
``searchsorted`` for both sides at once.

TPU adaptation (DESIGN §4): binary search needs data-dependent gathers,
which the VPU dislikes; instead each (probe-block, key-block) grid cell does
a masked-compare **reduction** — ``lo += [key < probe]``,
``hi += [key <= probe]`` — on the VPU with no gathers.  The keys of a block
are staged in SMEM and walked one scalar at a time; each scalar is compared
against a lane-dense block of probes held in vregs.  The key axis is
``arbitrary`` (sequential) and accumulates straight into the resident output
block.  O(N) compares per probe replace O(log N) gathers, a trade that only
pays while N * M stays small (see ``max_pairs`` in ``repro.kernels.tuning``).

int64 keys are compared as two int32 words split outside the kernel
(``common.int64_words``); int32 keys use one word.
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
    int64_words,
    round_up,
)
from repro.kernels.tuning import block_config

__all__ = ["semijoin_probe", "probe_fits"]


def probe_fits(n_keys: int, n_probes: int) -> bool:
    """Whether the compare kernel is within its work envelope (per worker)."""
    cfg = block_config("semijoin_probe")
    return (round_up(n_keys, cfg["block_n"]) * round_up(n_probes, TILE)
            <= cfg["max_pairs"])


def _kernel(*refs, n_words: int, key_rows: int):
    key_refs = refs[:n_words]
    probes = [r[...] for r in refs[n_words:2 * n_words]]
    lo_ref, hi_ref = refs[2 * n_words:]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    def one_key(r, l, acc):
        lo, hi = acc
        k0 = key_refs[0][r, l]
        if n_words == 1:
            lt = k0 < probes[0]
            le = k0 <= probes[0]
        else:
            k1 = key_refs[1][r, l]
            below = k0 < probes[0]
            tie = k0 == probes[0]
            lt = below | (tie & (k1 < probes[1]))
            le = below | (tie & (k1 <= probes[1]))
        return lo + lt.astype(jnp.int32), hi + le.astype(jnp.int32)

    lo, hi = for_each_scalar(key_rows, one_key, (lo_ref[...], hi_ref[...]))
    lo_ref[...] = lo
    hi_ref[...] = hi


def semijoin_probe(
    keys: jax.Array,  # (N,) sorted int32/int64 composite keys, max-padded
    probes: jax.Array,  # (M,) probe keys (same dtype as keys)
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (lo, hi): match range per probe, each (M,) int32.

    ``interpret=None`` auto-detects: compiled on TPU, interpreter elsewhere.
    ``block_m``/``block_n`` default to the per-platform table
    (``repro.kernels.tuning``); both are whole multiples of ``TILE``.
    """
    if interpret is None:
        interpret = default_interpret()
    cfg = block_config("semijoin_probe")
    block_m = block_m or cfg["block_m"]
    block_n = block_n or cfg["block_n"]
    check_block("block_m", block_m)
    check_block("block_n", block_n)
    if keys.dtype != probes.dtype:
        raise TypeError(f"keys {keys.dtype} vs probes {probes.dtype}")
    n = keys.shape[0]
    m = probes.shape[0]
    block_m = min(block_m, round_up(m, TILE))
    n_pad = round_up(n, block_n)
    m_pad = round_up(m, block_m)
    big = jnp.iinfo(keys.dtype).max
    key_words = [w.reshape(n_pad // LANES, LANES) for w in
                 int64_words(jnp.pad(keys, (0, n_pad - n),
                                     constant_values=big))]
    probe_words = [as_rows(w, m_pad, 0) for w in int64_words(probes)]
    n_words = len(key_words)
    key_rows, probe_rows = block_n // LANES, block_m // LANES
    grid = (m_pad // block_m, n_pad // block_n)

    key_spec = pl.BlockSpec((key_rows, LANES),
                            lambda i, j: (j, jnp.int32(0)),
                            memory_space=pltpu.SMEM)
    probe_spec = pl.BlockSpec((probe_rows, LANES),
                              lambda i, j: (i, jnp.int32(0)))
    lo, hi = pl.pallas_call(
        functools.partial(_kernel, n_words=n_words, key_rows=key_rows),
        grid=grid,
        in_specs=[key_spec] * n_words + [probe_spec] * n_words,
        out_specs=[probe_spec, probe_spec],
        out_shape=[jax.ShapeDtypeStruct((m_pad // LANES, LANES),
                                        jnp.int32)] * 2,
        compiler_params=compiler_params(interpret, ("parallel", "arbitrary")),
        interpret=interpret,
        name="semijoin_probe",
    )(*key_words, *probe_words)
    return lo.reshape(-1)[:m], hi.reshape(-1)[:m]
