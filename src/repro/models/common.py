"""Shared model components: config schema, norms, RoPE, initializers.

All modules are pure functions over explicit parameter pytrees (no framework
dependency); compute dtype is pinned per-config (bf16 by default) and never
inherits from the x64 flag the RDF engine enables.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# single definition of the project's shard_map flags, shared with the RDF
# execution substrate (repro.core.substrate); re-exported for callers
from repro.compat import shard_map

__all__ = [
    "MoEConfig",
    "SSMConfig",
    "HybridConfig",
    "EncDecConfig",
    "VLMConfig",
    "AdaptiveConfig",
    "ModelConfig",
    "rms_norm",
    "rope_tables",
    "apply_rope",
    "dense_init",
    "shape_of",
    "shard_map",
]


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    n_shared: int = 0  # shared (always-on) experts
    d_expert: int = 0  # expert FFN width (0 -> use d_ff)
    capacity_factor: float = 1.25
    router_noise: float = 0.0


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk: int = 256


@dataclass(frozen=True)
class HybridConfig:
    """RecurrentGemma-style: repeating (recurrent, recurrent, attention)."""

    pattern: tuple[str, ...] = ("rec", "rec", "attn")
    lru_width: int = 0  # 0 -> d_model
    window: int = 2048  # local attention window


@dataclass(frozen=True)
class EncDecConfig:
    n_enc_layers: int = 4
    n_frames: int = 1500  # audio frames after the (stubbed) conv frontend


@dataclass(frozen=True)
class VLMConfig:
    n_patches: int = 256  # visual tokens from the (stubbed) ViT frontend
    d_vision: int = 1024


@dataclass(frozen=True)
class AdaptiveConfig:
    """The paper's technique applied to LM lookups (DESIGN §2b)."""

    embedding_hot_budget: int = 0  # replicated hot embedding rows (0 = off)
    embedding_cold_frac: float = 1.0  # static cold-exchange capacity fraction
    expert_replication: int = 0  # number of hot experts replicated


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | ssm | hybrid | moe | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // n_heads
    qkv_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    hybrid: HybridConfig | None = None
    encdec: EncDecConfig | None = None
    vlm: VLMConfig | None = None
    adaptive: AdaptiveConfig | None = None
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: bool = True  # activation checkpointing per block
    remat_policy: str = "full"  # "full" | "dots" (save matmul outputs)
    # Unroll layer/chunk scans in the lowered HLO.  Used by the roofline
    # harness: XLA's HloCostAnalysis counts while-loop bodies ONCE, so flops
    # of scanned stacks are invisible; the harness compiles small unrolled
    # depth variants and extrapolates (see benchmarks/roofline.py).
    scan_unroll: bool = False

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def cdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    # --------------------------------------------------- parameter counting
    def param_count(self) -> int:
        """Approximate N for 6*N*D model-FLOPs accounting (dense matmuls)."""
        d, hd = self.d_model, self.hd
        att = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) + (
            self.n_heads * hd
        ) * d
        if self.moe:
            de = self.moe.d_expert or self.d_ff
            ffn = (self.moe.n_experts + self.moe.n_shared) * 3 * d * de
            ffn += d * self.moe.n_experts  # router
        else:
            ffn = 3 * d * self.d_ff
        per_layer = att + ffn + 2 * d
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb

    def active_param_count(self) -> int:
        """N_active for MoE (routed experts counted at top_k of n_experts)."""
        if not self.moe:
            return self.param_count()
        d = self.d_model
        hd = self.hd
        de = self.moe.d_expert or self.d_ff
        att = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) + (
            self.n_heads * hd
        ) * d
        ffn = (self.moe.top_k + self.moe.n_shared) * 3 * d * de
        per_layer = att + ffn + 2 * d
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb


# ------------------------------------------------------------------ layers
def rms_norm(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * g.astype(dt)


def rope_tables(positions: jax.Array, head_dim: int, theta: float,
                dtype=jnp.float32) -> tuple[jax.Array, jax.Array]:
    """(..., hd/2) cos/sin tables for the given positions."""
    half = head_dim // 2
    freqs = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., T, H, hd); cos/sin: (T, hd/2) or broadcastable (..., T, hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., :, None, :].astype(x.dtype)
    s = sin[..., :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def dense_init(key: jax.Array, shape: tuple[int, ...], dtype,
               scale: float | None = None) -> jax.Array:
    fan_in = shape[0] if len(shape) >= 2 else 1
    if len(shape) == 3:  # stacked expert / layer weights: fan over axis 1
        fan_in = shape[1]
    sd = scale if scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * sd).astype(dtype)


def shape_of(tree: Any) -> int:
    """Total parameter count of a pytree."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
