"""The dense decoder LM family: what the benchmark needs to make its
weights, to map its configuration onto the program, to run the plain
reference, and to count its work.

The harness finds this module by the configuration file's ``family``
key (``bench/families/<family>.py``); a configuration of another family
brings a module of its own with the same names: ``Dims``,
``PROGRAM_FIELDS``, ``make_params``, ``hidden``, ``head_blocks`` and
the work functions of ``bench/work.py``.

Weights.  They are an input of the run, like the traffic: made from
``--seed`` alone, on the device, in one jitted call, in the dtype they
are served in.  Each layer's tensors come from that layer's own key,
so ``layer(dims, seed, i)`` makes exactly what the stacked call makes
for layer ``i``: the reference remakes the weights one layer at a time
from the seed and never sees the program's arrays.  Every matrix is
drawn from N(0, 1/fan_in), fan_in being the dimension the matrix
contracts over; every norm gain is 1.  The embedding, which a tied
model also uses as its head, is N(0, 1/d_model), so the logits have
unit scale.  The tree has the layout the program's dense LM reads
(``embed``, ``final_norm``, ``lm_head`` unless the embedding is tied,
and ``blocks`` stacked over layers with ``ln1``, ``ln2``, ``attn``
{wq, wk, wv, wo} and ``mlp`` {wi, wg, wo}).

Reference.  Token embedding, then per layer RMSNorm, grouped-query
attention with rotary positions (rotate-half over the first
``partial_rotary_factor`` of each head), a causal mask and softmax, the
output projection and a residual, then RMSNorm and the SiLU-gated MLP
and a residual; a final RMSNorm and the head.  Written from the
configuration file alone, in float32 at ``highest`` matmul precision,
one sequence and one layer at a time.  With ``fp8`` every matmul
operand is rounded to float8 e4m3 with one scale per tensor: the
control.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.work import (Dims, attention_flops, decode_attn_bytes,  # noqa: F401
                        decode_token_flops, kv_bytes_per_token,
                        prefill_flops, weight_bytes)

# configuration-file key -> the program's ModelConfig field
PROGRAM_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
    "vocab_size": "vocab", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_base", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype", "sliding_window": "sliding_window",
    "hidden_act": "act",
}

HEAD_ROWS = 256          # positions per block of the head's matmul
FP8_MAX = 448.0          # largest finite float8_e4m3fn


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def weight_key(seed: int) -> jax.Array:
    """The root key of a run's weights: 32 bits drawn from ``seed``,
    so any whole number, however large, gives a valid key."""
    bits = np.random.SeedSequence(seed).generate_state(1)[0]
    return jax.random.PRNGKey(int(bits))


def _normal(key, shape, fan_in: int, dtype):
    scale = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _layer_from_key(key, dims: Dims):
    d, h, kh, dh, f = dims.d_model, dims.n_heads, dims.n_kv_heads, \
        dims.head_dim, dims.d_ff
    dt = dims.dtype
    k = jax.random.split(key, 7)
    return {
        "ln1": jnp.ones((d,), dt),
        "ln2": jnp.ones((d,), dt),
        "attn": {"wq": _normal(k[0], (d, h, dh), d, dt),
                 "wk": _normal(k[1], (d, kh, dh), d, dt),
                 "wv": _normal(k[2], (d, kh, dh), d, dt),
                 "wo": _normal(k[3], (h, dh, d), h * dh, dt)},
        "mlp": {"wi": _normal(k[4], (d, f), d, dt),
                "wg": _normal(k[5], (d, f), d, dt),
                "wo": _normal(k[6], (f, d), f, dt)},
    }


def _keys(seed: int, n_layers: int):
    k_embed, k_head, k_layers = jax.random.split(weight_key(seed), 3)
    return k_embed, k_head, jax.random.split(k_layers, n_layers)


def make_params(dims: Dims, seed: int, shard=None):
    """The whole tree, made on the device by one jitted call; ``shard``
    maps the tree's shapes to its shardings on a mesh."""
    keys = _keys(seed, dims.n_layers)

    def build(k_embed, k_head, k_layers):
        d, vp, dt = dims.d_model, dims.vocab_padded, dims.dtype
        tree = {
            "embed": _normal(k_embed, (vp, d), d, dt),
            "final_norm": jnp.ones((d,), dt),
            "blocks": jax.vmap(lambda k: _layer_from_key(k, dims))(k_layers),
        }
        if not dims.tied:
            tree["lm_head"] = _normal(k_head, (d, vp), d, dt)
        return tree

    out = None if shard is None else shard(jax.eval_shape(build, *keys))
    return jax.jit(build, out_shardings=out)(*keys)


def embed_and_head(dims: Dims, seed: int):
    """(embed (V, d), head (d, V)) as ``make_params`` makes them; a
    tied model's head is its embedding, transposed."""
    k_embed, k_head, _ = _keys(seed, dims.n_layers)
    d, vp, dt = dims.d_model, dims.vocab_padded, dims.dtype

    def build(a, b):
        embed = _normal(a, (vp, d), d, dt)
        return embed, (embed.T if dims.tied
                       else _normal(b, (d, vp), d, dt))

    return jax.jit(build)(k_embed, k_head)


def layer(dims: Dims, seed: int, i: int):
    """Layer ``i``'s tensors as ``make_params`` makes them."""
    _, _, k_layers = _keys(seed, dims.n_layers)
    return jax.jit(lambda k: _layer_from_key(k, dims))(k_layers[i])


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _q8(a):
    """``a`` rounded to float8 e4m3 with one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(spec, a, b, fp8: bool):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, gain, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * gain.astype(jnp.float32)


def _rope(x, pos, theta, rotary: int):
    """Rotate-half rotary embedding over the first ``rotary`` dims of
    each head; the rest pass through.  x (S, H, dh)."""
    half = rotary // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv          # (S, half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest],
                           axis=-1)


def _layer(x, w, *, dims: Dims, eps: float, theta: float, rotary: int,
           fp8: bool):
    """One decoder layer over one sequence x (S, d), causal."""
    s = x.shape[0]
    kh, g, dh = dims.n_kv_heads, dims.n_heads // dims.n_kv_heads, \
        dims.head_dim
    pos = jnp.arange(s)
    a = w["attn"]
    h = _rms(x, w["ln1"], eps)
    q = _rope(_ein("sd,dhk->shk", h, a["wq"], fp8), pos, theta, rotary)
    k = _rope(_ein("sd,dhk->shk", h, a["wk"], fp8), pos, theta, rotary)
    v = _ein("sd,dhk->shk", h, a["wv"], fp8)
    q = q.reshape(s, kh, g, dh)
    logits = _ein("skgd,tkd->kgst", q, k, fp8) / math.sqrt(dh)
    causal = pos[None, :] <= pos[:, None]
    logits = jnp.where(causal[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = _ein("kgst,tkd->skgd", p, v, fp8).reshape(s, dims.n_heads, dh)
    x = x + _ein("shk,hkd->sd", out, a["wo"], fp8)
    m = w["mlp"]
    h = _rms(x, w["ln2"], eps)
    gate = _ein("sd,df->sf", h, m["wg"], fp8)
    up = _ein("sd,df->sf", h, m["wi"], fp8)
    return x + _ein("sf,fd->sd", jax.nn.silu(gate) * up, m["wo"], fp8)


def hidden(conf: Dict[str, Any], seed: int, seqs: List[np.ndarray],
           length: int, fp8: bool = False) -> List[jnp.ndarray]:
    """Final-normed hidden states (length, d) of each sequence, padded
    at the end to ``length`` (causal: padding changes no earlier row)."""
    if conf.get("rope_scaling") is not None:
        raise ValueError("the reference has no rotary scaling")
    dims = Dims.from_config(conf)
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    rotary = int(dims.head_dim * conf.get("partial_rotary_factor", 1.0))
    embed, _ = embed_and_head(dims, seed)
    pad = [np.pad(s, (0, length - len(s))) for s in seqs]
    xs = [jnp.take(embed, jnp.asarray(p), axis=0).astype(jnp.float32)
          for p in pad]
    del embed
    step = jax.jit(lambda x, w: _layer(x, w, dims=dims, eps=eps,
                                       theta=theta, rotary=rotary, fp8=fp8))
    for i in range(dims.n_layers):
        w = layer(dims, seed, i)
        xs = [step(x, w) for x in xs]
    gain = jnp.ones((dims.d_model,), jnp.float32)
    return [_rms(x, gain, eps) for x in xs]


def head_blocks(conf: Dict[str, Any], seed: int, rows: jnp.ndarray,
                fp8: bool = False):
    """Logits over the true vocabulary for ``rows`` (n, d), in blocks:
    yields (first row, logits)."""
    dims = Dims.from_config(conf)
    _, head = embed_and_head(dims, seed)
    head = head[:, :dims.vocab]
    f = jax.jit(lambda r, w: _ein("nd,dv->nv", r, w, fp8))
    for i in range(0, rows.shape[0], HEAD_ROWS):
        yield i, f(rows[i:i + HEAD_ROWS], head)
