"""Layout rules and helpers shared by the data-plane Pallas kernels.

Every kernel keeps its vector operands lane-dense: a 1-D array of ``n``
int32 values travels as ``(n / 128, 128)`` rows, and every block is a whole
number of ``(8, 128)`` int32 tiles (``TILE`` values).  That is the layout
XLA gives a 1-D int32 array on TPU, so a block never needs a relayout, and
it stays legal under ``vmap`` (the worker and batch axes become squeezed
leading block dimensions).  Scalars the kernels walk one at a time (sorted
keys, input rows) are staged in SMEM; nothing inside a kernel is 64-bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "LANES",
    "TILE",
    "default_interpret",
    "compiler_params",
    "round_up",
    "check_block",
    "as_rows",
    "int64_words",
    "for_each_scalar",
]

LANES = 128
TILE = 8 * LANES  # one int32 vreg


def default_interpret() -> bool:
    """Interpret off-TPU (CPU/GPU tests, parity runs); compiled on TPU."""
    return jax.default_backend() != "tpu"


def compiler_params(interpret: bool, semantics: tuple[str, ...]):
    """Mosaic grid semantics for a compiled kernel; None when interpreted."""
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics)


def round_up(n: int, m: int) -> int:
    return -(-max(n, 1) // m) * m


def check_block(name: str, size: int) -> None:
    if size <= 0 or size % TILE:
        raise ValueError(
            f"{name}={size} must be a positive multiple of {TILE} "
            "(whole int32 tiles)"
        )


def as_rows(x: jax.Array, n_pad: int, fill) -> jax.Array:
    """Pad a 1-D array to ``n_pad`` with ``fill`` and fold it into
    lane-dense ``(n_pad / 128, 128)`` rows."""
    n = x.shape[0]
    if n_pad != n:
        x = jnp.pad(x, (0, n_pad - n), constant_values=fill)
    return x.reshape(n_pad // LANES, LANES)


def int64_words(x: jax.Array) -> tuple[jax.Array, ...]:
    """Order-preserving int32 words of an integer array.

    int32 input is its own single word.  int64 input splits into
    (high word, low word - 2**31): comparing the pairs lexicographically as
    signed int32 orders them exactly like the int64 values, so a kernel
    compares 64-bit keys without any 64-bit type."""
    if x.dtype == jnp.int32:
        return (x,)
    if x.dtype != jnp.int64:
        raise TypeError(f"expected int32 or int64 keys, got {x.dtype}")
    hi = (x >> 32).astype(jnp.int32)
    lo = ((x & 0xFFFFFFFF) - (1 << 31)).astype(jnp.int32)
    return hi, lo


def for_each_scalar(n_rows: int, body, carry, unroll: int = 8):
    """``carry = body(r, l, carry)`` for every (row, lane) of an
    ``(n_rows, 128)`` SMEM block, in row-major order.

    The loop counter and indices are int32 (the engine runs with x64 on,
    where a Python-int ``fori_loop`` would count in int64), and ``unroll``
    lanes are unrolled per iteration (Mosaic unrolls all or nothing)."""
    per_row = LANES // unroll
    shift = per_row.bit_length() - 1  # indices by shift/mask: Mosaic-safe

    def step(i, c):
        r = i >> shift
        l0 = (i & (per_row - 1)) * unroll
        for u in range(unroll):
            c = body(r, l0 + u, c)
        return c

    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_rows * per_row),
                             step, carry)
