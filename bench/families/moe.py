"""The DeepSeekMoE family: what the benchmark needs to make its weights,
to map its configuration onto the program, to run the plain routed
reference, and to count its work.  The same names as
``bench/families/dense.py``, which the harness looks for by the
configuration file's ``family`` key.

The model.  ``first_k_dense_replace`` leading layers (one) are dense
decoder layers with a SiLU-gated MLP of ``intermediate_size``; every
later layer keeps the attention and replaces the MLP by a mixture of
experts: a softmax gate over ``n_routed_experts``, the greedy top
``num_experts_per_tok`` of it, each chosen expert's SiLU-gated MLP of
``moe_intermediate_size`` weighted by its gate probability (the chosen
weights renormalised to sum to 1 only when ``norm_topk_prob``), plus
``n_shared_experts`` shared experts that every token passes through,
which together are one SiLU-gated MLP of ``n_shared_experts x
moe_intermediate_size``.

Weights.  As in ``dense.py``: made from ``--seed`` alone, on the
device, in one jitted call, each layer from its own key (layer 0's
tensors exactly as ``dense.py`` makes a layer), so ``layer(dims, seed,
i)`` remakes layer ``i`` alone.  Every matrix is N(0, 1/fan_in); the
router is N(0, 1/hidden_size) and float32, as the program holds it.
The tree is the program's: ``first_block`` (layer 0, with a leading
axis of 1), and ``blocks`` stacked over the MoE layers with ``ln1``,
``ln2``, ``attn`` and ``moe`` {``router``, ``experts`` {wi, wg, wo}
stacked over the experts, ``shared`` {wi, wg, wo}}.

Reference.  Written from the configuration file alone, in float32 at
``highest`` matmul precision, one sequence and one layer at a time.
Layer 0 is ``dense.py``'s layer.  A MoE layer is ``dense.py``'s
attention, then: the gate's softmax over every routed expert, its top
k, the chosen probabilities as published (renormalised only under
``norm_topk_prob``) scattered into an (S, experts) weight table that
is 0 for every expert a token did not choose; every expert's MLP over
every token, its hidden rows scaled by that table before the down
projection, so an expert a token did not choose adds exactly nothing;
and the shared MLP.  Nothing is dropped and no capacity exists.  With
``fp8`` every matmul operand is rounded to float8 e4m3 with one scale
per tensor (the gate's too): the control.
"""

import dataclasses
import functools
import math
from typing import Any, Dict, List, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from bench.families import dense
from bench.work import (DTYPES, attention_flops,  # noqa: F401
                        decode_attn_bytes, kv_bytes_per_token)

# configuration-file key -> the program's ModelConfig field
PROGRAM_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "first_layer_dense_ff",
    "n_routed_experts": "n_experts", "num_experts_per_tok": "top_k",
    "n_shared_experts": "n_shared_experts",
    "moe_intermediate_size": "moe_d_ff",
    "norm_topk_prob": "norm_topk_prob",
    "vocab_size": "vocab", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_base", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype", "hidden_act": "act",
}

head_blocks = dense.head_blocks


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a DeepSeekMoE LM, from a configuration file.
    ``d_ff`` is the leading dense layer's MLP width."""

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
    n_experts: int
    top_k: int
    n_shared: int
    moe_d_ff: int
    norm_topk_prob: bool

    n_dense = 1                 # leading dense layers

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense

    @property
    def dtype(self):
        return DTYPES[self.dtype_name][0]

    @property
    def itemsize(self) -> int:
        return DTYPES[self.dtype_name][1]

    @classmethod
    def from_config(cls, conf: Mapping[str, Any]) -> "Dims":
        layout = (conf["first_k_dense_replace"], conf["moe_layer_freq"],
                  conf["scoring_func"])
        if layout != (cls.n_dense, 1, "softmax"):
            raise ValueError(f"the program runs one leading dense layer, "
                             f"then a softmax-gated MoE in every layer; "
                             f"the file says (first_k_dense_replace, "
                             f"moe_layer_freq, scoring_func) = {layout}")
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
                   tied=conf["tie_word_embeddings"],
                   n_experts=conf["n_routed_experts"],
                   top_k=conf["num_experts_per_tok"],
                   n_shared=conf["n_shared_experts"],
                   moe_d_ff=conf["moe_intermediate_size"],
                   norm_topk_prob=conf["norm_topk_prob"])


# ---------------------------------------------------------------------------
# work: what a token needs, counting only the experts it is routed to
# ---------------------------------------------------------------------------

def _attn_params(m: Dims) -> int:
    return m.d_model * m.head_dim * (2 * m.n_heads + 2 * m.n_kv_heads)


def active_matmul_params(m: Dims) -> int:
    """Weights one token multiplies through, all layers: attention, the
    dense layer's MLP, and in each MoE layer the gate, its ``top_k``
    routed experts and the shared experts."""
    expert = 3 * m.d_model * m.moe_d_ff
    moe = m.d_model * m.n_experts + (m.top_k + m.n_shared) * expert
    return (m.n_layers * _attn_params(m)
            + m.n_dense * 3 * m.d_model * m.d_ff + m.n_moe * moe)


def weight_bytes(m: Dims) -> int:
    """Bytes of the served weights: every expert, the float32 router,
    norms, embedding and (unless tied) head over the padded vocabulary."""
    expert = 3 * m.d_model * m.moe_d_ff
    layers = (m.n_layers * (_attn_params(m) + 2 * m.d_model)
              + m.n_dense * 3 * m.d_model * m.d_ff
              + m.n_moe * (m.n_experts + m.n_shared) * expert)
    tables = (1 if m.tied else 2) * m.vocab_padded * m.d_model
    router = m.n_moe * m.d_model * m.n_experts * 4
    return (layers + tables + m.d_model) * m.itemsize + router


def decode_token_flops(m: Dims, live: int) -> int:
    """Model FLOPs of one decoded token that attends ``live``
    positions: the weights it is routed through, attention, and the
    head over the true vocabulary."""
    return (2 * active_matmul_params(m) + attention_flops(m, live)
            + 2 * m.d_model * m.vocab)


def prefill_flops(m: Dims, tokens: int) -> int:
    """Model FLOPs of a causal prefill of ``tokens`` positions (no
    head), each token through its routed experts only."""
    causal = tokens * (tokens + 1) // 2
    return (2 * active_matmul_params(m) * tokens
            + m.n_layers * 4 * m.n_heads * m.head_dim * causal)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _moe_layer_from_key(key, dims: Dims):
    d, h, kh, dh = dims.d_model, dims.n_heads, dims.n_kv_heads, \
        dims.head_dim
    e, f, fs = dims.n_experts, dims.moe_d_ff, dims.n_shared * dims.moe_d_ff
    dt = dims.dtype
    k = jax.random.split(key, 11)
    normal = dense._normal
    return {
        "ln1": jnp.ones((d,), dt),
        "ln2": jnp.ones((d,), dt),
        "attn": {"wq": normal(k[0], (d, h, dh), d, dt),
                 "wk": normal(k[1], (d, kh, dh), d, dt),
                 "wv": normal(k[2], (d, kh, dh), d, dt),
                 "wo": normal(k[3], (h, dh, d), h * dh, dt)},
        "moe": {"router": normal(k[4], (d, e), d, jnp.float32),
                "experts": {"wi": normal(k[5], (e, d, f), d, dt),
                            "wg": normal(k[6], (e, d, f), d, dt),
                            "wo": normal(k[7], (e, f, d), f, dt)},
                "shared": {"wi": normal(k[8], (d, fs), d, dt),
                           "wg": normal(k[9], (d, fs), d, dt),
                           "wo": normal(k[10], (fs, d), fs, dt)}},
    }


def _layer_from_key(key, dims: Dims, i: int):
    if i < dims.n_dense:
        return dense._layer_from_key(key, dims)
    return _moe_layer_from_key(key, dims)


def make_params(dims: Dims, seed: int, shard=None):
    """The whole tree, made on the device by one jitted call; ``shard``
    maps the tree's shapes to its shardings on a mesh."""
    keys = dense._keys(seed, dims.n_layers)

    def build(k_embed, k_head, k_layers):
        d, vp, dt = dims.d_model, dims.vocab_padded, dims.dtype
        first = dense._layer_from_key(k_layers[0], dims)
        tree = {
            "embed": dense._normal(k_embed, (vp, d), d, dt),
            "final_norm": jnp.ones((d,), dt),
            "first_block": jax.tree.map(lambda a: a[None], first),
            "blocks": jax.vmap(lambda k: _moe_layer_from_key(k, dims))(
                k_layers[dims.n_dense:]),
        }
        if not dims.tied:
            tree["lm_head"] = dense._normal(k_head, (d, vp), d, dt)
        return tree

    out = None if shard is None else shard(jax.eval_shape(build, *keys))
    return jax.jit(build, out_shardings=out)(*keys)


@functools.lru_cache(maxsize=None)
def _layer_maker(dims: Dims, i: int):
    return jax.jit(lambda k: _layer_from_key(k, dims, i))


def layer(dims: Dims, seed: int, i: int):
    """Layer ``i``'s tensors as ``make_params`` makes them."""
    _, _, k_layers = dense._keys(seed, dims.n_layers)
    return _layer_maker(dims, min(i, dims.n_dense))(k_layers[i])


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _attention(x, w, *, dims: Dims, eps: float, theta: float, rotary: int,
               fp8: bool):
    """x plus one layer's causal attention over x (S, d): as in
    ``dense._layer``."""
    ein, s = dense._ein, x.shape[0]
    kh, g, dh = dims.n_kv_heads, dims.n_heads // dims.n_kv_heads, \
        dims.head_dim
    pos = jnp.arange(s)
    a = w["attn"]
    h = dense._rms(x, w["ln1"], eps)
    q = dense._rope(ein("sd,dhk->shk", h, a["wq"], fp8), pos, theta, rotary)
    k = dense._rope(ein("sd,dhk->shk", h, a["wk"], fp8), pos, theta, rotary)
    v = ein("sd,dhk->shk", h, a["wv"], fp8)
    q = q.reshape(s, kh, g, dh)
    logits = ein("skgd,tkd->kgst", q, k, fp8) / math.sqrt(dh)
    causal = pos[None, :] <= pos[:, None]
    logits = jnp.where(causal[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = ein("kgst,tkd->skgd", p, v, fp8).reshape(s, dims.n_heads, dh)
    return x + ein("shk,hkd->sd", out, a["wo"], fp8)


def _moe_layer(x, w, *, dims: Dims, eps: float, theta: float, rotary: int,
               fp8: bool):
    """One MoE decoder layer over one sequence x (S, d), causal."""
    ein = dense._ein
    x = _attention(x, w, dims=dims, eps=eps, theta=theta, rotary=rotary,
                   fp8=fp8)
    h = dense._rms(x, w["ln2"], eps)
    m = w["moe"]
    probs = jax.nn.softmax(ein("sd,de->se", h, m["router"], fp8), axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, dims.top_k)
    if dims.norm_topk_prob:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    gates = jnp.zeros_like(probs).at[rows, top_ids].set(top_w)   # (S, E)
    e = m["experts"]
    hid = (jax.nn.silu(ein("sd,edf->sef", h, e["wg"], fp8))
           * ein("sd,edf->sef", h, e["wi"], fp8))
    routed = ein("sef,efd->sd", hid * gates[:, :, None], e["wo"], fp8)
    sh = m["shared"]
    hid = (jax.nn.silu(ein("sd,df->sf", h, sh["wg"], fp8))
           * ein("sd,df->sf", h, sh["wi"], fp8))
    return x + routed + ein("sf,fd->sd", hid, sh["wo"], fp8)


@functools.lru_cache(maxsize=None)
def _steps(dims: Dims, eps: float, theta: float, fp8: bool):
    """The jitted (dense, MoE) layer steps, made once for each run's
    check to reuse."""
    kw = dict(dims=dims, eps=eps, theta=theta, rotary=dims.head_dim,
              fp8=fp8)
    return (jax.jit(lambda x, w: dense._layer(x, w, **kw)),
            jax.jit(lambda x, w: _moe_layer(x, w, **kw)))


def hidden(conf: Dict[str, Any], seed: int, seqs: List[np.ndarray],
           length: int, fp8: bool = False) -> List[jnp.ndarray]:
    """Final-normed hidden states (length, d) of each sequence, padded
    at the end to ``length`` (causal: padding changes no earlier row)."""
    if conf.get("rope_scaling") is not None:
        raise ValueError("the reference has no rotary scaling")
    dims = Dims.from_config(conf)
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    embed, _ = dense.embed_and_head(dims, seed)
    pad = [np.pad(s, (0, length - len(s))) for s in seqs]
    xs = [jnp.take(embed, jnp.asarray(p), axis=0).astype(jnp.float32)
          for p in pad]
    del embed
    steps = _steps(dims, eps, theta, fp8)
    for i in range(dims.n_layers):
        w = layer(dims, seed, i)
        step = steps[i >= dims.n_dense]
        xs = [step(x, w) for x in xs]
    gain = jnp.ones((dims.d_model,), jnp.float32)
    return [dense._rms(x, gain, eps) for x in xs]
