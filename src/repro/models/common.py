"""Shared model-config schema and primitive layers for the pod path.

Every assigned architecture is described by one ``ModelConfig``; builders
in lm.py / ssm.py / hybrid.py / encdec.py / vlm.py assemble families from
these primitives.  All parameters are plain dict pytrees; sharding specs
are produced by ``repro.distributed.sharding`` from the same config.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str                     # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None  # default d_model // n_heads
    # attention details
    qk_norm: bool = False
    rope_base: float = 10000.0
    sliding_window: Optional[int] = None   # decode window for long_500k
    prefix_lm: bool = False                # PaliGemma-style prefix masking
    # activation / norm
    act: str = "silu"               # silu (SwiGLU) | gelu
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int = 0
    first_layer_dense_ff: int = 0   # deepseek: dense layer 0
    capacity_factor: float = 1.25   # training only: serving is dropless
    norm_topk_prob: bool = True     # renormalise the top-k gate weights
    # SSM (Mamba-2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_groups: int = 1
    # hybrid (Zamba2): shared attention block period
    shared_attn_every: int = 0
    # enc-dec (Whisper)
    n_encoder_layers: int = 0
    n_audio_ctx: int = 0            # encoder positions (stub frontend)
    # VLM (PaliGemma)
    n_vision_tokens: int = 0        # patch embeddings from the stub
    d_vision: int = 1152            # SigLIP-So400m width (stub output)
    # numerics
    dtype: str = "bfloat16"
    # provenance
    source: str = ""

    @property
    def dh(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def ssm_heads(self) -> int:
        return (self.ssm_expand * self.d_model) // self.ssm_head_dim

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    def jnp_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    def n_params(self) -> int:
        """Analytic parameter count (used for 6·N·D MODEL_FLOPS)."""
        d, v = self.d_model, self.vocab
        emb = v * d * (1 if self.tie_embeddings else 2)
        per_layer = 0
        # attention block
        attn = d * self.n_heads * self.dh + 2 * d * self.n_kv_heads * self.dh \
            + self.n_heads * self.dh * d
        if self.family == "ssm":
            per_layer = self._ssm_block_params()
            total = emb + self.n_layers * per_layer
        elif self.family == "hybrid":
            mamba = self._ssm_block_params()
            shared = attn + 3 * d * self.d_ff + 4 * d
            n_shared_uses = (self.n_layers // self.shared_attn_every
                             if self.shared_attn_every else 0)
            total = emb + self.n_layers * mamba + shared
        elif self.family in ("moe",):
            moe = (self.n_experts * 3 * d * self.moe_d_ff
                   + self.n_shared_experts * 3 * d * self.moe_d_ff
                   + d * self.n_experts)
            total = emb + self.n_layers * (attn + moe + 2 * d)
            if self.first_layer_dense_ff:
                total += 3 * d * self.first_layer_dense_ff \
                    - (self.n_experts * 3 * d * self.moe_d_ff
                       + d * self.n_experts)
        else:
            ff_mult = 3 if self.act == "silu" else 2
            per_layer = attn + ff_mult * d * self.d_ff + 2 * d
            n_l = self.n_layers + self.n_encoder_layers
            total = emb + n_l * per_layer
            if self.n_encoder_layers:            # cross-attention
                total += self.n_layers * attn
        return int(total)

    def n_active_params(self) -> int:
        """Active params per token (MoE: only top_k experts count)."""
        if self.family != "moe":
            return self.n_params()
        d = self.d_model
        full = self.n_params()
        all_experts = self.n_layers * self.n_experts * 3 * d * self.moe_d_ff
        active = self.n_layers * self.top_k * 3 * d * self.moe_d_ff
        return int(full - all_experts + active)

    def _ssm_block_params(self) -> int:
        d, di = self.d_model, self.d_inner
        n, g, h = self.ssm_state, self.ssm_groups, self.ssm_heads
        in_proj = d * (2 * di + 2 * g * n + h)
        conv = (di + 2 * g * n) * self.ssm_conv
        return in_proj + conv + 3 * h + di * d + d


# ---------------------------------------------------------------------------
# primitives (pure functions over param dicts)
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray,
             eps: float = 1e-6) -> jnp.ndarray:
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = (x.astype(jnp.float32) * jax.lax.rsqrt(ms + eps)).astype(x.dtype)
    return y * gamma.astype(x.dtype)


def layer_norm(x, gamma, beta, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return (((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
            * gamma.astype(x.dtype) + beta.astype(x.dtype))


def rope_cos_sin(positions: jnp.ndarray, dim: int, base: float,
                 dtype=jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions: (...,) int32 -> cos/sin (..., dim//2)."""
    half = dim // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray,
               sin: jnp.ndarray) -> jnp.ndarray:
    """x: (..., S, H, D); cos/sin: (S, D//2), (..., S, D//2), or
    broadcastable with a head axis already in place.  A missing head
    axis is inserted — without it, per-slot decode positions of shape
    (B, 1, D//2) would right-align against (B, S, H, D//2) and rotate
    EVERY slot by slot 0's position (the preempt-to-a-different-slot
    tests in tests/test_preemption.py pin this down)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim < x.ndim:                  # (..., S, half): add head axis
        cos = cos[..., None, :]
        sin = sin[..., None, :]
    c = cos.astype(x.dtype)
    s = sin.astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def dense_init(key, shape, scale: Optional[float] = None,
               dtype=jnp.float32) -> jnp.ndarray:
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale
            ).astype(dtype)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                       mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """logits (B,S,V) f32-upcast CE against labels (B,S).

    Written to stay vocab-sharded under GSPMD: ``take_along_axis`` over
    a model-sharded vocab axis makes the partitioner all-gather the
    full-vocab f32 logits per device (tens of GB at 200k vocab); the
    iota/where reduction and a hand-rolled logsumexp keep every (B,S,V)
    intermediate sharded and reduce to small (B,S) all-reduces.
    """
    logits = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    logz = m[..., 0] + jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                    logits.ndim - 1)
    gold = jnp.sum(jnp.where(iota == labels[..., None], logits, 0.0),
                   axis=-1)
    nll = logz - gold
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()
