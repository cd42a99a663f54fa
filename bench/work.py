"""Operations and bytes a step of a dense decoder LM needs, from shapes
and live lengths (the dense family's arithmetic; another family's module
in ``bench/families/`` brings its own), and the roofline time of a
kernel from its operations and bytes.

The numbers count the work the model requires, not what an
implementation happens to do: attention reads the positions a token
attends to (its live length), never the cache's capacity or the blocks
a kernel fetches, and a prefill counts its prompt's tokens, never the
bucket it was padded to.  So a roofline share built on them reads the
same work whatever implements the kernel.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax.numpy as jnp

DTYPES = {"bfloat16": (jnp.bfloat16, 2), "float32": (jnp.float32, 4)}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense decoder LM, from a configuration file."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    vocab_padded: int
    dtype_name: str
    tied: bool

    @property
    def dtype(self):
        return DTYPES[self.dtype_name][0]

    @property
    def itemsize(self) -> int:
        return DTYPES[self.dtype_name][1]

    @classmethod
    def from_config(cls, conf: Mapping[str, Any]) -> "Dims":
        heads = conf["num_attention_heads"]
        return cls(n_layers=conf["num_hidden_layers"],
                   d_model=conf["hidden_size"], n_heads=heads,
                   n_kv_heads=conf["num_key_value_heads"],
                   head_dim=conf.get("head_dim",
                                     conf["hidden_size"] // heads),
                   d_ff=conf["intermediate_size"],
                   vocab=conf["vocab_size"],
                   vocab_padded=conf["vocab_padded"],
                   dtype_name=conf["torch_dtype"],
                   tied=conf["tie_word_embeddings"])


def layer_matmul_params(m: Dims) -> int:
    """Weights one token multiplies through in one layer (q, k, v, o and
    the gated MLP)."""
    attn = m.d_model * m.head_dim * (2 * m.n_heads + 2 * m.n_kv_heads)
    return attn + 3 * m.d_model * m.d_ff


def weight_bytes(m: Dims) -> int:
    """Bytes of the served weights: layers, norms, embedding and (unless
    tied) head over the padded vocabulary."""
    layers = m.n_layers * (layer_matmul_params(m) + 2 * m.d_model)
    tables = (1 if m.tied else 2) * m.vocab_padded * m.d_model
    return (layers + tables + m.d_model) * m.itemsize


def kv_bytes_per_token(m: Dims) -> int:
    """Cache bytes one position holds over all layers (K and V)."""
    return m.n_layers * 2 * m.n_kv_heads * m.head_dim * m.itemsize


def attention_flops(m: Dims, live: int) -> int:
    """QK^T and PV of one query over ``live`` positions, all layers."""
    return m.n_layers * 4 * m.n_heads * m.head_dim * live


def decode_token_flops(m: Dims, live: int) -> int:
    """Model FLOPs of one decoded token that attends ``live`` positions:
    every layer's matmuls, attention, and the head over the true
    vocabulary."""
    return (2 * m.n_layers * layer_matmul_params(m)
            + attention_flops(m, live) + 2 * m.d_model * m.vocab)


def prefill_flops(m: Dims, tokens: int) -> int:
    """Model FLOPs of a causal prefill of ``tokens`` positions (no head:
    the first output token comes from the decode step)."""
    causal = tokens * (tokens + 1) // 2
    return (2 * m.n_layers * layer_matmul_params(m) * tokens
            + m.n_layers * 4 * m.n_heads * m.head_dim * causal)


def decode_attn_bytes(m: Dims, live: int) -> int:
    """Least HBM bytes decode attention moves for one query: the K and V
    of its ``live`` positions, its q read and its output written, all
    layers."""
    return (live * kv_bytes_per_token(m)
            + m.n_layers * 2 * m.n_heads * m.head_dim * m.itemsize)


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> float:
    """The least time the chip could take: the larger of the compute
    and the memory bound."""
    return max(flops / peak_flops, nbytes / peak_bw)
