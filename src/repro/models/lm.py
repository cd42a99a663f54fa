"""Decoder-only LM family (dense GQA + MoE) — the pod-path model.

Covers phi4-mini, phi3-mini, qwen3-32b, yi-6b (dense) and
deepseek-moe-16b, qwen3-moe-30b-a3b (MoE).  PaliGemma reuses these blocks
through models/vlm.py (prefix-LM masking), Whisper through
models/encdec.py (cross-attention), Zamba2 through models/hybrid.py
(shared attention block).

Design notes (TPU-native, see DESIGN.md §6):
  * layers are **scan-stacked**: every per-layer parameter carries a
    leading ``L`` dim and the forward pass is one ``lax.scan`` — keeps
    HLO size O(1) in depth so 64-layer dry-runs lower fast.
  * attention is **query-chunked** (flash-attention structure in pure
    jnp): causal logits are never materialized beyond
    (B, H, chunk, S) — prefill_32k and train_4k stay within VMEM-scale
    transients instead of the O(S²) mask path.
  * GQA uses grouped einsums (no ``jnp.repeat`` of K/V to H heads).
  * MoE uses per-group capacity dispatch (Switch-style): tokens are
    grouped by data shard, top-k routed, gathered to (G, E, C, D) and
    expert-matmul'd with experts sharded on the ``model`` axis — the
    (data → model) reshard of the dispatch tensor is the all-to-all.
    Training (``lm_loss``) caps each expert at ``capacity_factor``;
    the serving steps (prefill, chunk, decode) are dropless: each
    expert's capacity is every token of the group, so a request's
    output never depends on what the other slots route.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.act_sharding import (gather_expert_weights,
                                            shard_act, shard_expert,
                                            shard_group, shard_heads,
                                            shard_kv, shard_logits,
                                            shard_seq)

from .common import (ModelConfig, apply_rope, cross_entropy_loss,
                     dense_init, rms_norm, rope_cos_sin, split_keys)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# vocab padding (model-axis shardability: pad to a multiple of 2048 =
# 16 shards x 128 lanes)
# ---------------------------------------------------------------------------

VOCAB_PAD = 2048


def padded_vocab(cfg: ModelConfig) -> int:
    return -(-cfg.vocab // VOCAB_PAD) * VOCAB_PAD


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_attn_block(key, cfg: ModelConfig, dtype, n_layers: int):
    """Stacked attention params: leading dim = n_layers."""
    d, h, kh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.dh
    ks = split_keys(key, 4)
    L = n_layers
    p = {
        "wq": dense_init(ks[0], (L, d, h, dh), dtype=dtype),
        "wk": dense_init(ks[1], (L, d, kh, dh), dtype=dtype),
        "wv": dense_init(ks[2], (L, d, kh, dh), dtype=dtype),
        "wo": dense_init(ks[3], (L, h, dh, d),
                         scale=1.0 / math.sqrt(h * dh), dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((L, dh), dtype)
        p["k_norm"] = jnp.ones((L, dh), dtype)
    return p


GATED_ACTS = ("silu", "geglu")


def _gate(act: str, g):
    return jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)


def _init_mlp(key, d: int, f: int, act: str, dtype, lead=()):
    ks = split_keys(key, 3)
    p = {"wi": dense_init(ks[0], (*lead, d, f), dtype=dtype),
         "wo": dense_init(ks[1], (*lead, f, d),
                          scale=1.0 / math.sqrt(f), dtype=dtype)}
    if act in GATED_ACTS:
        p["wg"] = dense_init(ks[2], (*lead, d, f), dtype=dtype)
    return p


def _init_moe(key, cfg: ModelConfig, dtype, n_layers: int):
    d, e, fe = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = split_keys(key, 3)
    L = n_layers
    p = {
        "router": dense_init(ks[0], (L, d, e), scale=0.02, dtype=jnp.float32),
        "experts": _init_mlp(ks[1], d, fe, cfg.act, dtype, lead=(L, e)),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * fe
        p["shared"] = _init_mlp(ks[2], d, fs, cfg.act, dtype, lead=(L,))
    return p


def init_lm(key, cfg: ModelConfig) -> Params:
    dtype = cfg.jnp_dtype()
    vp = padded_vocab(cfg)
    d = cfg.d_model
    ks = split_keys(key, 8)
    n_moe = cfg.n_layers - (1 if cfg.first_layer_dense_ff else 0)
    params: Params = {
        "embed": dense_init(ks[0], (vp, d), scale=0.02, dtype=dtype),
        "final_norm": jnp.ones((d,), dtype),
    }
    L = n_moe if cfg.n_experts else cfg.n_layers
    blocks = {
        "ln1": jnp.ones((L, d), dtype),
        "ln2": jnp.ones((L, d), dtype),
        "attn": _init_attn_block(ks[1], cfg, dtype, L),
    }
    if cfg.n_experts:
        blocks["moe"] = _init_moe(ks[2], cfg, dtype, L)
    else:
        blocks["mlp"] = _init_mlp(ks[2], d, cfg.d_ff, cfg.act, dtype,
                                  lead=(L,))
    params["blocks"] = blocks
    if cfg.first_layer_dense_ff:
        params["first_block"] = {
            "ln1": jnp.ones((1, d), dtype),
            "ln2": jnp.ones((1, d), dtype),
            "attn": _init_attn_block(ks[3], cfg, dtype, 1),
            "mlp": _init_mlp(ks[4], d, cfg.first_layer_dense_ff, cfg.act,
                             dtype, lead=(1,)),
        }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[5], (d, vp), scale=0.02,
                                       dtype=dtype)
    return params


# ---------------------------------------------------------------------------
# attention — query-chunked causal/prefix/windowed (flash structure, jnp)
# ---------------------------------------------------------------------------

def _proj_qkv(p: Params, cfg: ModelConfig, x, positions):
    """x (B,S,D) -> q (B,S,H,dh), k/v (B,S,KH,dh) with qk_norm + RoPE."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope_base:
        cos, sin = rope_cos_sin(positions, cfg.dh, cfg.rope_base)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def chunked_attention(q, k, v, cfg: ModelConfig, *,
                      prefix_len: int = 0,
                      window: Optional[int] = None,
                      chunk: int = 512) -> jnp.ndarray:
    """Causal (+prefix, +sliding-window) attention, O(S·chunk) transients.

    q (B,S,H,dh); k,v (B,S,KH,dh).  Returns (B,S,H,dh).

    GQA is handled by expanding K/V to the FLAT head dim (jnp.repeat)
    instead of reshaping Q to (KH, G, dh): the flat H axis stays
    model-sharded under GSPMD (H=64 shards 16-way; the grouped (8,8)
    reshape forced a resharding — §Perf iteration q1), and the expanded
    K/V are H-sharded so their per-device footprint is the same as the
    grouped form.
    """
    b, s, h, dh = q.shape
    kh = k.shape[2]
    g = h // kh
    chunk = min(chunk, s)
    n_chunks = s // chunk
    assert s % chunk == 0, (s, chunk)
    scale = 1.0 / math.sqrt(dh)
    kx = shard_kv(jnp.repeat(k, g, axis=2)) if g > 1 else shard_kv(k)
    vx = shard_kv(jnp.repeat(v, g, axis=2)) if g > 1 else shard_kv(v)
    q = shard_heads(q)
    kpos = jnp.arange(s)

    def body(carry, qc_and_start):
        qc, start = qc_and_start           # (B,chunk,H,dh), ()
        qpos = start + jnp.arange(chunk)
        logits = jnp.einsum("bqhd,bshd->bhqs", qc, kx,
                            preferred_element_type=jnp.float32)
        logits = logits * scale
        mask = kpos[None, :] <= qpos[:, None]
        if prefix_len:
            mask = mask | (kpos[None, :] < prefix_len)
        if window is not None:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        logits = jnp.where(mask[None, None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhqs,bshd->bqhd", w, vx)
        return carry, out

    qs = q.reshape(b, n_chunks, chunk, h, dh).transpose(1, 0, 2, 3, 4)
    starts = jnp.arange(n_chunks) * chunk
    # checkpoint the chunk body: without this, autodiff saves the softmax
    # weights of EVERY chunk — the full S^2 attention matrix — as scan
    # residuals (flash-attention recomputes instead; so do we)
    _, outs = jax.lax.scan(jax.checkpoint(body), None, (qs, starts))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dh)
    return out


def attention_block(p: Params, cfg: ModelConfig, x, *,
                    prefix_len: int = 0,
                    window: Optional[int] = None) -> jnp.ndarray:
    b, s, _ = x.shape
    q, k, v = _proj_qkv(p, cfg, x, jnp.arange(s))
    out = chunked_attention(q, k, v, cfg, prefix_len=prefix_len,
                            window=window)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"])


# ---------------------------------------------------------------------------
# decode attention (one token, ring KV cache)
# ---------------------------------------------------------------------------

def _attend_ring(p: Params, cfg: ModelConfig, q, kc, vc, n_valid, dtype,
                 attn_impl=None) -> jnp.ndarray:
    """q (B,1,H,dh) over one layer's ring kc/vc (B,KH,C,dh), masked to
    the first ``n_valid`` (B,) positions, then the output projection:
    (B,1,D).  ``attn_impl`` replaces the einsum attention (§4.8)."""
    b = q.shape[0]
    h, kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    g = h // kh
    c = kc.shape[2]
    if attn_impl is not None:
        out = attn_impl(q[:, 0], kc, vc, n_valid).reshape(b, 1, h, dh)
    else:
        qg = q[:, 0].reshape(b, kh, g, dh)
        scale = 1.0 / math.sqrt(dh)
        logits = jnp.einsum("bkgd,bkcd->bkgc", qg, kc,
                            preferred_element_type=jnp.float32) * scale
        pos = jnp.arange(c)[None, None, None, :]
        valid = pos < n_valid[:, None, None, None]
        logits = jnp.where(valid, logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1).astype(dtype)
        out = jnp.einsum("bkgc,bkcd->bkgd", w, vc).reshape(b, 1, h, dh)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"])


def decode_attention_block(p: Params, cfg: ModelConfig, x, cache_k, cache_v,
                           lengths, attn_impl=None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                      jnp.ndarray]:
    """x (B,1,D); cache_k/v (B,KH,C,dh); lengths (B,) = tokens already in
    context (the new token's absolute position).  Ring-buffer update.
    Returns (out (B,1,D), new_k, new_v).

    ``attn_impl`` is the vendor-kernel hook (§4.8): when provided it
    replaces only the attention math — called as
    ``attn_impl(q (B,H,dh), kc, vc, n_valid) -> (B,H,dh)`` over the
    already-updated cache; the ring update and output projection stay
    identical to the reference path."""
    c = cache_k.shape[2]
    q, k, v = _proj_qkv(p, cfg, x, lengths[:, None])
    slot = (lengths % c).astype(jnp.int32)
    onehot = jax.nn.one_hot(slot, c, dtype=x.dtype)          # (B,C)
    kc = cache_k * (1 - onehot)[:, None, :, None] \
        + k[:, 0].transpose(0, 1, 2)[:, :, None, :] * onehot[:, None, :, None]
    vc = cache_v * (1 - onehot)[:, None, :, None] \
        + v[:, 0][:, :, None, :] * onehot[:, None, :, None]
    n_valid = jnp.minimum(lengths + 1, c)
    y = _attend_ring(p, cfg, q, kc, vc, n_valid, x.dtype, attn_impl)
    return y, kc, vc


def _write_rows(cache, rows, layer, slot):
    """Write ``rows`` (B,KH,dh) into the stacked ring cache
    (L,B,KH,C,dh) at ``(layer, b, :, slot[b], :)``: one
    dynamic-update-slice per slot over the static B, which XLA does in
    place on a carried, donated buffer.  (A batched ``.at[].set``
    scatter instead led XLA to a transposed cache layout and two
    whole-cache copies a step.)"""
    upd = rows.astype(cache.dtype)[:, None, None, :, None, :]
    for b in range(rows.shape[0]):
        cache = jax.lax.dynamic_update_slice(
            cache, upd[b], (layer, b, 0, slot[b], 0))
    return cache


def decode_attention_layer(p: Params, cfg: ModelConfig, x, cache_k,
                           cache_v, layer, lengths, attn_impl=None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                      jnp.ndarray]:
    """``decode_attention_block`` for layer ``layer`` (a traced int32) of
    the stacked ring cache_k/v (L,B,KH,C,dh): the new token's K/V rows
    are written in place at ring position ``lengths % C`` — every
    slot's, inactive ones included — and attention reads that layer's
    view.  Returns (out (B,1,D), cache_k, cache_v), bit-identical to
    the one-hot update (an IEEE ``old*1 + new*0`` is ``old``)."""
    c = cache_k.shape[3]
    q, k, v = _proj_qkv(p, cfg, x, lengths[:, None])
    slot = (lengths % c).astype(jnp.int32)
    cache_k = _write_rows(cache_k, k[:, 0], layer, slot)
    cache_v = _write_rows(cache_v, v[:, 0], layer, slot)
    kc = jax.lax.dynamic_index_in_dim(cache_k, layer, keepdims=False)
    vc = jax.lax.dynamic_index_in_dim(cache_v, layer, keepdims=False)
    n_valid = jnp.minimum(lengths + 1, c)
    y = _attend_ring(p, cfg, q, kc, vc, n_valid, x.dtype, attn_impl)
    return y, cache_k, cache_v


# ---------------------------------------------------------------------------
# decode attention — paged KV (block pool + per-slot block table)
# ---------------------------------------------------------------------------

def paged_decode_attention_block(p: Params, cfg: ModelConfig, x,
                                 pool_k, pool_v, tables, lengths,
                                 attn_impl=None
                                 ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                            jnp.ndarray]:
    """Paged twin of ``decode_attention_block``: the slot's KV rows
    live scattered across a shared physical block pool instead of one
    contiguous ring.

    x (B,1,D); pool_k/pool_v (P,KH,BS,dh) — ONE layer's physical
    blocks; tables (B,T) int32 physical block ids in logical order
    (T*BS = the slot's logical ring capacity, entry 0 = the pool's
    garbage block for unmapped tail entries); lengths (B,) absolute
    positions.  Returns (out (B,1,D), new_pool_k, new_pool_v).

    The new token's K/V land at logical ring position ``lengths % c``
    → physical ``(tables[b, pos // BS], pos % BS)`` — a scatter, which
    is value-identical to the contiguous path's one-hot multiply-add
    (an IEEE ``k*1 + cache*0`` is exactly ``k``/``cache``).  The
    reference attention gathers the table back to a contiguous
    (B,KH,c,dh) view and runs the EXACT einsum/mask/softmax sequence
    of the contiguous block, so decoded values are bit-identical;
    ``attn_impl`` (the vendor-kernel hook, §4.8) instead receives the
    pool + table and walks blocks natively:
    ``attn_impl(q (B,H,dh), pool_k, pool_v, tables, n_valid)``."""
    b = x.shape[0]
    h, kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    g = h // kh
    bs = pool_k.shape[2]
    t = tables.shape[1]
    c = t * bs
    q, k, v = _proj_qkv(p, cfg, x, lengths[:, None])
    pos = (lengths % c).astype(jnp.int32)
    phys = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    # duplicate phys ids only ever collide on the garbage block (active
    # slots own disjoint blocks), so last-write-wins is harmless there
    pool_k = pool_k.at[phys, :, off].set(k[:, 0].astype(pool_k.dtype))
    pool_v = pool_v.at[phys, :, off].set(v[:, 0].astype(pool_v.dtype))
    n_valid = jnp.minimum(lengths + 1, c)
    if attn_impl is not None:
        out = attn_impl(q[:, 0], pool_k, pool_v, tables,
                        n_valid).reshape(b, 1, h, dh)
    else:
        kc = pool_k[tables].transpose(0, 2, 1, 3, 4).reshape(b, kh, c, dh)
        vc = pool_v[tables].transpose(0, 2, 1, 3, 4).reshape(b, kh, c, dh)
        qg = q[:, 0].reshape(b, kh, g, dh)
        scale = 1.0 / math.sqrt(dh)
        logits = jnp.einsum("bkgd,bkcd->bkgc", qg, kc,
                            preferred_element_type=jnp.float32) * scale
        posc = jnp.arange(c)[None, None, None, :]
        valid = posc < n_valid[:, None, None, None]
        logits = jnp.where(valid, logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        out = jnp.einsum("bkgc,bkcd->bkgd", w, vc).reshape(b, 1, h, dh)
    y = jnp.einsum("bqhk,hkd->bqd", out, p["wo"])
    return y, pool_k, pool_v


def lm_decode_paged(params: Params, cfg: ModelConfig, pool: Dict,
                    tables, tokens, lengths, *, data_shards: int = 16,
                    embed_scale: Optional[float] = None, attn_impl=None):
    """One decode step over the paged KV pool.  tokens (B,1); lengths
    (B,); tables (B,T) int32; pool {k,v}: (L,P,KH,BS,dh).  Returns
    (logits (B,V), new_pool).  The block tables and lengths are traced
    arguments — mapping/unmapping blocks (slot growth, admission,
    retirement, checkpoint restore) changes VALUES only, so this
    program is traced exactly once per engine (the compile-once
    discipline of the lane masks, applied to KV placement)."""
    x = embed_tokens(params, cfg, tokens)
    if embed_scale is not None:
        x = x * jnp.asarray(embed_scale, x.dtype)
    i0 = 0
    if "first_block" in params:
        fb = jax.tree.map(lambda a: a[0], params["first_block"])
        xin = rms_norm(x, fb["ln1"], cfg.norm_eps)
        att, kc, vc = paged_decode_attention_block(
            fb["attn"], cfg, xin, pool["k"][0], pool["v"][0],
            tables, lengths, attn_impl=attn_impl)
        h = x + att
        hin = rms_norm(h, fb["ln2"], cfg.norm_eps)
        x = h + mlp_block(fb["mlp"], cfg, hin)
        first_kv = (kc, vc)
        i0 = 1

    def body(h, layer_in):
        p_l, pk, pv = layer_in
        xin = rms_norm(h, p_l["ln1"], cfg.norm_eps)
        att, kc, vc = paged_decode_attention_block(
            p_l["attn"], cfg, xin, pk, pv, tables, lengths,
            attn_impl=attn_impl)
        hh = h + att
        hin = rms_norm(hh, p_l["ln2"], cfg.norm_eps)
        if "moe" in p_l:
            y, _ = moe_block(p_l["moe"], cfg, hin, data_shards)
        else:
            y = mlp_block(p_l["mlp"], cfg, hin)
        return hh + y, (kc, vc)

    x, (ks_, vs_) = jax.lax.scan(body, x,
                                 (params["blocks"], pool["k"][i0:],
                                  pool["v"][i0:]))
    if i0:
        ks_ = jnp.concatenate([first_kv[0][None], ks_])
        vs_ = jnp.concatenate([first_kv[1][None], vs_])
    logits = lm_logits(params, cfg, x)[:, 0]
    return logits, {"k": ks_, "v": vs_}


def lm_prefill_chunk_paged(params: Params, cfg: ModelConfig, pool: Dict,
                           table_row, tokens, start, *,
                           window: Optional[int] = None,
                           embed_scale: Optional[float] = None,
                           data_shards: int = 16) -> Dict:
    """Paged twin of ``lm_prefill_chunk``: gather ONE slot's blocks to
    a contiguous batch=1 cache, run the exact contiguous chunk math,
    and scatter the result back into the pool.

    table_row (T,) int32 is the slot's block table; ``start`` stays a
    traced scalar and the gather/scatter indices are traced values, so
    one compiled program serves every chunk of every slot whatever
    blocks it holds.  Unmapped trailing entries point at the garbage
    block: the gather reads garbage rows the chunk's causal mask never
    attends (positions beyond ``start + S``), and the scatter writes
    them back to the garbage block where nothing reads them."""
    bs = pool["k"].shape[3]
    t = table_row.shape[0]

    def gather(p):                       # (L,P,KH,BS,dh) -> (L,1,KH,C,dh)
        l, _, kh, _, dh = p.shape
        one = p[:, table_row].transpose(0, 2, 1, 3, 4)
        return one.reshape(l, kh, t * bs, dh)[:, None]

    cache1 = {"k": gather(pool["k"]), "v": gather(pool["v"])}
    cache1 = lm_prefill_chunk(params, cfg, cache1, tokens, start,
                              window=window, embed_scale=embed_scale,
                              data_shards=data_shards)

    def scatter(p, one):                 # inverse of gather
        l, _, kh, _, dh = p.shape
        src = one[:, 0].reshape(l, kh, t, bs, dh).transpose(0, 2, 1, 3, 4)
        return p.at[:, table_row].set(src.astype(p.dtype))

    return {"k": scatter(pool["k"], cache1["k"]),
            "v": scatter(pool["v"], cache1["v"])}


# ---------------------------------------------------------------------------
# FFN — dense (SwiGLU / GELU)
# ---------------------------------------------------------------------------

def mlp_block(p: Params, cfg: ModelConfig, x) -> jnp.ndarray:
    hidden = jnp.einsum("bsd,df->bsf", x, p["wi"])
    if cfg.act in GATED_ACTS:
        gate = jnp.einsum("bsd,df->bsf", x, p["wg"])
        hidden = _gate(cfg.act, gate) * hidden
    else:
        hidden = jax.nn.gelu(hidden)
    return jnp.einsum("bsf,fd->bsd", hidden, p["wo"])


# ---------------------------------------------------------------------------
# FFN — MoE (per-group capacity dispatch, Switch-style)
# ---------------------------------------------------------------------------

def moe_groups(n_tokens: int, data_shards: int = 16) -> int:
    """Group count for capacity dispatch: one group per data shard when
    groups stay usefully large, else a single global group."""
    if n_tokens >= 16 * 1024:
        return data_shards
    return 1


def moe_capacity(cfg: ModelConfig, tokens_per_group: int,
                 capped: bool = False) -> int:
    """Queue slots per expert and group: every token of the group (a
    token picks an expert at most once, so nothing is dropped), or when
    ``capped`` (training) ``capacity_factor`` times the fair share, at
    least 4 and a multiple of 4."""
    if not capped:
        return tokens_per_group
    c = int(math.ceil(tokens_per_group * cfg.top_k * cfg.capacity_factor
                      / cfg.n_experts))
    return max(4, -(-c // 4) * 4)          # >=4, multiple of 4


def moe_dispatch(router_logits, cfg: ModelConfig, capacity: int,
                 n_valid=None):
    """router_logits (G,T,E) -> (dispatch_idx (G,E*C) int32 token ids
    [T = dropped], combine (G,E*C) weights, aux_loss scalar).  The
    combine weights are the top-k softmax probabilities, renormalised
    to sum to 1 only when ``cfg.norm_topk_prob``.

    Masked mode (bucketed MoE prefill): when ``n_valid`` (a TRACED
    scalar) is given, T is a right-PADDED token count — the compiled
    shape — and tokens at flat positions >= ``n_valid`` are dropped
    outright.  Right-padding appends to the END of the cumsum order,
    so with a dropless capacity (every token of the bucket) each real
    token keeps exactly the queue position the unpadded dispatch at
    the true length gives it: one compile per bucket, bit-identical
    expert routing per true length."""
    g, t, e = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, cfg.top_k)          # (G,T,K)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch):  E * sum_e f_e * p_e
    density = jnp.mean(jax.nn.one_hot(top_ids[..., 0], e), axis=1)   # (G,E)
    p_mean = jnp.mean(probs, axis=1)                                  # (G,E)
    aux = jnp.mean(jnp.sum(density * p_mean, axis=-1)) * e

    flat_ids = top_ids.reshape(g, t * cfg.top_k)              # (G,TK)
    flat_w = top_w.reshape(g, t * cfg.top_k)
    # position of each (token,k) within its expert queue
    onehot = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)     # (G,TK,E)
    pos_in_e = jnp.cumsum(onehot, axis=1) - 1                 # (G,TK,E)
    pos = jnp.take_along_axis(pos_in_e, flat_ids[..., None],
                              axis=-1)[..., 0]                # (G,TK)
    token_of = jnp.arange(t * cfg.top_k) // cfg.top_k         # (TK,)
    keep = pos < capacity
    if n_valid is not None:
        keep = keep & (token_of[None, :] < n_valid)
    slot = flat_ids * capacity + pos                          # (G,TK)
    slot = jnp.where(keep, slot, e * capacity)                # overflow bin
    # scatter token ids into slots; default T = dummy token
    dispatch = jnp.full((g, e * capacity + 1), t, jnp.int32)
    combine = jnp.zeros((g, e * capacity + 1), jnp.float32)
    gi = jnp.arange(g)[:, None]
    dispatch = dispatch.at[gi, slot].set(
        jnp.broadcast_to(token_of, (g, t * cfg.top_k)).astype(jnp.int32),
        mode="drop")
    combine = combine.at[gi, slot].set(flat_w, mode="drop")
    return dispatch[:, :-1], combine[:, :-1], aux


def moe_block(p: Params, cfg: ModelConfig, x,
              data_shards: int = 16, n_valid=None,
              capped: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,S,D) -> (y, aux_loss).  Expert-parallel capacity dispatch.
    Every expert's queue holds the group's tokens, so no (token, expert)
    pair is dropped; ``capped`` (training's ``_layer_fwd``) caps it at
    ``capacity_factor`` instead.

    When capped, an activation-sharding context is active and shapes
    divide, delegates to the shard_map all-to-all implementation
    (§Perf C4) — explicit EP collectives instead of GSPMD-inferred ones.

    ``n_valid`` (a TRACED scalar) switches ``moe_dispatch`` into its
    masked mode for bucketed-prefill serving: S is a right-padded
    bucket length and only the first ``n_valid`` tokens route — see
    ``moe_dispatch``.  Masked mode keeps the single-group layout
    (token positions across groups would not survive padding)."""
    b, s, d = x.shape
    from .moe_ep import ep_applicable, moe_block_ep
    if n_valid is None and capped and ep_applicable(cfg, b, s):
        return moe_block_ep(p, cfg, x)
    t_all = b * s
    g = moe_groups(t_all, data_shards)
    if n_valid is not None and g != 1:
        raise ValueError("masked dispatch requires the single-group "
                         "layout (got %d groups)" % g)
    t = t_all // g
    xg = shard_group(x.reshape(g, t, d))
    cap = moe_capacity(cfg, t, capped)
    with jax.named_scope("moe.router"):
        logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                            p["router"])
        dispatch, combine, aux = moe_dispatch(logits, cfg, cap,
                                              n_valid=n_valid)
    with jax.named_scope("moe.experts"):
        # pad a zero token row for dropped/dummy slots
        xpad = jnp.concatenate([xg, jnp.zeros((g, 1, d), xg.dtype)],
                               axis=1)
        xe = jnp.take_along_axis(xpad, dispatch[..., None],
                                 axis=1)                     # (G,EC,D)
        # pin dispatch tensors expert-parallel: groups on data, experts
        # on model — the reshard from token-grouped to expert-parallel
        # IS the all-to-all; without the pins GSPMD replicates (§Perf C2)
        xe = shard_expert(xe.reshape(g, cfg.n_experts, cap, d))
        we = p["experts"]
        wi = gather_expert_weights(we["wi"])
        wo = gather_expert_weights(we["wo"])
        hid = jnp.einsum("gecd,edf->gecf", xe, wi)
        if cfg.act in GATED_ACTS:
            gate = jnp.einsum("gecd,edf->gecf", xe,
                              gather_expert_weights(we["wg"]))
            hid = _gate(cfg.act, gate) * hid
        else:
            hid = jax.nn.gelu(hid)
        hid = shard_expert(hid)
        ye = shard_expert(jnp.einsum("gecf,efd->gecd", hid, wo))
        ye = (ye.reshape(g, cfg.n_experts * cap, d)
              * combine[..., None].astype(ye.dtype))
        # combine back: scatter-add slots to tokens
        ypad = jnp.zeros((g, t + 1, d), ye.dtype)
        y = shard_group(
            ypad.at[jnp.arange(g)[:, None], dispatch].add(ye)[:, :t])
    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            sh = p["shared"]
            hid = jnp.einsum("gtd,df->gtf", xg, sh["wi"])
            if cfg.act in GATED_ACTS:
                gate = jnp.einsum("gtd,df->gtf", xg, sh["wg"])
                hid = _gate(cfg.act, gate) * hid
            else:
                hid = jax.nn.gelu(hid)
            y = y + jnp.einsum("gtf,fd->gtd", hid, sh["wo"])
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# transformer layers (scan-stacked)
# ---------------------------------------------------------------------------

def _layer_fwd(cfg: ModelConfig, x, p_l, *, prefix_len=0, window=None,
               data_shards: int = 16):
    # layer boundaries are sequence-parallel (§Perf A2): the remat
    # residual and the norm/elementwise traffic shard S over `model`;
    # GSPMD gathers before the projections and scatters after
    x = shard_seq(x)
    h = x + attention_block(p_l["attn"], cfg,
                            rms_norm(x, p_l["ln1"], cfg.norm_eps),
                            prefix_len=prefix_len, window=window)
    h = shard_seq(h)
    hin = rms_norm(h, p_l["ln2"], cfg.norm_eps)
    if "moe" in p_l:
        y, aux = moe_block(p_l["moe"], cfg, hin, data_shards, capped=True)
    elif "mlp" in p_l:
        y, aux = mlp_block(p_l["mlp"], cfg, hin), 0.0
    return shard_seq(h + y), aux


def lm_backbone(params: Params, cfg: ModelConfig, x, *,
                prefix_len: int = 0, window: Optional[int] = None,
                remat: bool = False, data_shards: int = 16) -> Tuple:
    """Embedded input x (B,S,D) -> (hidden (B,S,D), aux_loss)."""
    aux_total = 0.0
    if "first_block" in params:
        fb = jax.tree.map(lambda a: a[0], params["first_block"])
        x, aux = _layer_fwd(cfg, x, fb, prefix_len=prefix_len, window=window,
                            data_shards=data_shards)
        aux_total += aux

    def body(carry, p_l):
        h, aux_acc = carry
        h, aux = _layer_fwd(cfg, h, p_l, prefix_len=prefix_len,
                            window=window, data_shards=data_shards)
        return (h, aux_acc + aux), None

    fn = jax.checkpoint(body) if remat else body
    (x, aux), _ = jax.lax.scan(fn, (x, jnp.float32(aux_total)),
                               params["blocks"])
    return x, aux


def lm_logits(params: Params, cfg: ModelConfig, h) -> jnp.ndarray:
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    return shard_logits(jnp.einsum("bsd,dv->bsv", h, head))


def embed_tokens(params: Params, cfg: ModelConfig, tokens) -> jnp.ndarray:
    return shard_act(jnp.take(params["embed"], tokens, axis=0))


# ---------------------------------------------------------------------------
# public steps
# ---------------------------------------------------------------------------

def lm_loss(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            *, remat: bool = True, data_shards: int = 16) -> Tuple:
    """batch: tokens (B,S) int32, labels (B,S) int32 (-1 = pad)."""
    x = embed_tokens(params, cfg, batch["tokens"])
    h, aux = lm_backbone(params, cfg, x, remat=remat,
                         data_shards=data_shards)
    logits = lm_logits(params, cfg, h)
    mask = (batch["labels"] >= 0).astype(jnp.float32)
    labels = jnp.maximum(batch["labels"], 0)
    loss = cross_entropy_loss(logits, labels, mask)
    metrics = {"ce_loss": loss, "aux_loss": aux}
    if cfg.n_experts:
        loss = loss + 0.01 * aux
    return loss, metrics


def lm_prefill(params: Params, cfg: ModelConfig, tokens,
               cache_len: Optional[int] = None, *,
               window: Optional[int] = None,
               prefix_len: int = 0, data_shards: int = 16,
               prefix_embed: Optional[jnp.ndarray] = None,
               embed_scale: Optional[float] = None,
               n_valid=None):
    """tokens (B,S) -> (last-token logits (B,V), cache dict).

    cache layout: k/v (L, B, KH, C, dh) ring-indexed by absolute pos.
    ``prefix_embed`` (B,P,D) prepends already-embedded tokens (VLM
    vision prefix); combined with ``prefix_len`` for prefix-LM masking.
    ``n_valid`` (a TRACED scalar) is the bucketed-MoE mode: S is a
    right-padded bucket length and ``n_valid`` the true token count;
    every ``moe_block`` routes only the first ``n_valid`` tokens, with
    dropless capacity, so one compile per bucket routes exactly what
    the true length routes (see ``moe_dispatch``).
    """
    x = embed_tokens(params, cfg, tokens)
    if embed_scale is not None:
        x = x * jnp.asarray(embed_scale, x.dtype)
    if prefix_embed is not None:
        x = jnp.concatenate([prefix_embed.astype(x.dtype), x], axis=1)
    b, s = x.shape[0], x.shape[1]
    c = cache_len or s
    # run backbone while capturing per-layer K/V
    kvs = []

    def layer_with_kv(x, p_l):
        xin = rms_norm(x, p_l["ln1"], cfg.norm_eps)
        q, k, v = _proj_qkv(p_l["attn"], cfg, xin, jnp.arange(s))
        out = chunked_attention(q, k, v, cfg, prefix_len=prefix_len,
                                window=window)
        h = x + jnp.einsum("bqhk,hkd->bqd", out, p_l["attn"]["wo"])
        hin = rms_norm(h, p_l["ln2"], cfg.norm_eps)
        if "moe" in p_l:
            y, _ = moe_block(p_l["moe"], cfg, hin, data_shards,
                             n_valid=n_valid)
        else:
            y = mlp_block(p_l["mlp"], cfg, hin)
        return h + y, (k, v)

    def scan_body(h, p_l):
        h, kv = layer_with_kv(h, p_l)
        return h, kv

    if "first_block" in params:
        fb = jax.tree.map(lambda a: a[0], params["first_block"])
        x, kv0 = layer_with_kv(x, fb)
        kvs.append(kv0)
    x, (ks_, vs_) = jax.lax.scan(scan_body, x, params["blocks"])
    logits = lm_logits(params, cfg, x[:, -1:])[:, 0]

    def to_cache(k):                       # (B,S,KH,dh) -> (B,KH,C,dh)
        kc = jnp.zeros((b, cfg.n_kv_heads, c, cfg.dh), k.dtype)
        take = min(s, c)
        src = k[:, s - take:].transpose(0, 2, 1, 3)
        if c >= s:
            return jax.lax.dynamic_update_slice(kc, src, (0, 0, 0, 0))
        # ring: last c tokens land at slots (pos % c)
        pos = (jnp.arange(s - take, s) % c)
        return kc.at[:, :, pos].set(src)

    if kvs:
        k0, v0 = kvs[0]
        ks_ = jnp.concatenate([to_cache(k0)[None], jax.vmap(to_cache)(ks_)])
        vs_ = jnp.concatenate([to_cache(v0)[None], jax.vmap(to_cache)(vs_)])
    else:
        ks_ = jax.vmap(to_cache)(ks_)
        vs_ = jax.vmap(to_cache)(vs_)
    return logits, {"k": ks_, "v": vs_}


def lm_prefill_chunk(params: Params, cfg: ModelConfig, cache: Dict,
                     tokens, start, *, window: Optional[int] = None,
                     embed_scale: Optional[float] = None,
                     data_shards: int = 16) -> Dict:
    """One prompt CHUNK through the backbone: tokens (B,S) occupy
    absolute positions ``start .. start+S`` of a cache that already
    holds every earlier position.  Returns the updated cache only —
    the engine hands the last prompt token to the decode loop, so
    chunk steps never pay for logits.

    ``start`` is a TRACED scalar: one compiled program serves every
    chunk of every prompt (the chunked-prefill analogue of the masked
    pool's traced active mask).  The attention body mirrors
    ``chunked_attention``'s einsum/mask/softmax structure exactly —
    cache positions beyond the causal horizon are masked to -1e30,
    i.e. exactly-zero softmax weight — which is what keeps chunked
    prefill token-identical to one-shot prefill for families whose
    decode is length-masked (dense/vlm; see docs/PREEMPTION.md §4).
    Requires ``start + S <= cache_len`` (no ring wrap): the serving
    engine falls back to one-shot exact prefill past that."""
    x = embed_tokens(params, cfg, tokens)
    if embed_scale is not None:
        x = x * jnp.asarray(embed_scale, x.dtype)
    s = x.shape[1]
    g = cfg.n_heads // cfg.n_kv_heads
    positions = start + jnp.arange(s)
    scale = 1.0 / math.sqrt(cfg.dh)

    def attend(p_attn, xin, ck, cv):
        # ck/cv (B,KH,C,dh): write the chunk's K/V at its absolute
        # positions, then attend the chunk's queries over the cache
        c = ck.shape[2]
        q, k, v = _proj_qkv(p_attn, cfg, xin, positions)
        ck = jax.lax.dynamic_update_slice(
            ck, k.transpose(0, 2, 1, 3).astype(ck.dtype),
            (0, 0, start, 0))
        cv = jax.lax.dynamic_update_slice(
            cv, v.transpose(0, 2, 1, 3).astype(cv.dtype),
            (0, 0, start, 0))
        ks = ck.transpose(0, 2, 1, 3)          # (B,C,KH,dh)
        vs = cv.transpose(0, 2, 1, 3)
        kx = shard_kv(jnp.repeat(ks, g, axis=2)) if g > 1 else shard_kv(ks)
        vx = shard_kv(jnp.repeat(vs, g, axis=2)) if g > 1 else shard_kv(vs)
        qx = shard_heads(q)
        kpos = jnp.arange(c)
        logits = jnp.einsum("bqhd,bshd->bhqs", qx, kx,
                            preferred_element_type=jnp.float32)
        logits = logits * scale
        mask = kpos[None, :] <= positions[:, None]
        if window is not None:
            mask = mask & (kpos[None, :] > positions[:, None] - window)
        logits = jnp.where(mask[None, None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1).astype(vx.dtype)
        out = jnp.einsum("bhqs,bshd->bqhd", w, vx)
        y = jnp.einsum("bqhk,hkd->bqd", out, p_attn["wo"])
        return y, ck, cv

    def layer(h, p_l, ck, cv):
        xin = rms_norm(h, p_l["ln1"], cfg.norm_eps)
        att, ck, cv = attend(p_l["attn"], xin, ck, cv)
        hh = h + att
        hin = rms_norm(hh, p_l["ln2"], cfg.norm_eps)
        if "moe" in p_l:
            y, _ = moe_block(p_l["moe"], cfg, hin, data_shards)
        else:
            y = mlp_block(p_l["mlp"], cfg, hin)
        return hh + y, ck, cv

    i0 = 0
    if "first_block" in params:
        fb = jax.tree.map(lambda a: a[0], params["first_block"])
        x, k0, v0 = layer(x, fb, cache["k"][0], cache["v"][0])
        first_kv = (k0, v0)
        i0 = 1

    def body(h, layer_in):
        p_l, ck, cv = layer_in
        h, kc, vc = layer(h, p_l, ck, cv)
        return h, (kc, vc)

    x, (ks_, vs_) = jax.lax.scan(body, x,
                                 (params["blocks"], cache["k"][i0:],
                                  cache["v"][i0:]))
    if i0:
        ks_ = jnp.concatenate([first_kv[0][None], ks_])
        vs_ = jnp.concatenate([first_kv[1][None], vs_])
    return {"k": ks_, "v": vs_}


def lm_decode(params: Params, cfg: ModelConfig, cache: Dict, tokens,
              lengths, *, data_shards: int = 16,
              embed_scale: Optional[float] = None, attn_impl=None):
    """One decode step.  tokens (B,1); lengths (B,) absolute positions;
    cache {k,v}: (L,B,KH,C,dh).  Returns (logits (B,V), new_cache).
    The layer scan carries the stacked cache and each layer writes its
    new rows in place (``decode_attention_layer``), so on a donated
    cache the step moves only those rows.  ``attn_impl`` plumbs a
    vendor attention kernel into every layer (§4.8)."""
    x = embed_tokens(params, cfg, tokens)
    if embed_scale is not None:
        x = x * jnp.asarray(embed_scale, x.dtype)

    def layer(h, p_l, ck, cv, i):
        xin = rms_norm(h, p_l["ln1"], cfg.norm_eps)
        att, ck, cv = decode_attention_layer(p_l["attn"], cfg, xin, ck, cv,
                                             i, lengths, attn_impl=attn_impl)
        hh = h + att
        hin = rms_norm(hh, p_l["ln2"], cfg.norm_eps)
        if "moe" in p_l:
            y, _ = moe_block(p_l["moe"], cfg, hin, data_shards)
        else:
            y = mlp_block(p_l["mlp"], cfg, hin)
        return hh + y, ck, cv

    ck, cv = cache["k"], cache["v"]
    i0 = 0
    if "first_block" in params:
        fb = jax.tree.map(lambda a: a[0], params["first_block"])
        x, ck, cv = layer(x, fb, ck, cv, jnp.int32(0))
        i0 = 1

    def body(carry, p_l):
        h, ck, cv, i = carry
        h, ck, cv = layer(h, p_l, ck, cv, i)
        return (h, ck, cv, i + 1), None

    (x, ck, cv, _), _ = jax.lax.scan(body, (x, ck, cv, jnp.int32(i0)),
                                     params["blocks"])
    logits = lm_logits(params, cfg, x)[:, 0]
    return logits, {"k": ck, "v": cv}
