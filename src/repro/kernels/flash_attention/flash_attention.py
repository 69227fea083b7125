"""Pallas TPU flash-attention kernel (forward).

Canonical TPU structure: grid = (batch*heads, q_blocks, kv_blocks) with
``arbitrary`` semantics on the innermost axis; the online-softmax state
(m, l, acc) lives in VMEM scratch and persists across kv steps.  BlockSpecs
tile Q/K/V into (block_q, head_dim) / (block_kv, head_dim) VMEM tiles whose
last dims are MXU-aligned (head_dim padded to a multiple of 128 by the
wrapper in ops.py).

Validated on CPU in interpret mode against ref.py; on TPU the same code
compiles to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_fwd"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, block_q: int, block_kv: int,
            n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # (bq, d)
    k = k_ref[0].astype(jnp.float32)  # (bk, d)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0
        )
        kpos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1
        )
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _final():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,  # (BH, T, d)
    k: jax.Array,  # (BH, S, d)
    v: jax.Array,  # (BH, S, d)
    *,
    causal: bool = True,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = True,
) -> jax.Array:
    bh, t, d = q.shape
    s = k.shape[1]
    assert t % block_q == 0 and s % block_kv == 0, (t, s, block_q, block_kv)
    n_q = t // block_q
    n_kv = s // block_kv
    scale = d ** -0.5

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, n_kv=n_kv,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ) if not interpret else None,
        interpret=interpret,
    )(q, k, v)
