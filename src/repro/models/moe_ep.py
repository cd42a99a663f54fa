"""Expert-parallel MoE via shard_map + explicit all_to_all (§Perf C4).

The GSPMD einsum-dispatch path (lm.moe_block) lets the partitioner
choose the communication pattern; measured on qwen3-moe-30b-a3b
train_4k it falls back to THREE (G, T, D)-sized f32 collectives per
layer (~25 GB/device/layer) because the combine scatter-add cannot be
inferred as an all-to-all.  This module states the schedule explicitly:

  tokens  (per device: batch x seq shard)          [data, model]
    -> local top-k routing + capacity dispatch      (no comms)
    -> all_to_all over `model`: (E, C, D) -> (E/m, m*C, D)
    -> local expert FFN (weights all-gathered over `data` once: the
       FSDP gather, ~small vs activations)
    -> all_to_all back
    -> local combine (weighted scatter-add, T_loc-sized)

Per-device bytes moved ~ E*C_loc*D*2 per direction — the information-
theoretic minimum for capacity-based expert parallelism — instead of
the (G,T,D) all-reduce x3.  Differentiable end to end (all_to_all and
all_gather have transposes).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.act_sharding import _current as _act_ctx
from repro.models.common import ModelConfig


def ep_applicable(cfg: ModelConfig, b: int, s: int) -> bool:
    """shard_map EP path is usable for this call?"""
    ctx = _act_ctx()
    if ctx is None or not ctx.experts_divisible:
        return False
    mesh = ctx.mesh
    msz = mesh.shape.get("model", 1)
    dsz = 1
    for a in ("pod", "data"):
        dsz *= mesh.shape.get(a, 1)
    if msz <= 1:
        return False
    if not ctx.batch_divisible or b % dsz:
        return False
    if s % msz:
        return False
    if cfg.n_experts % msz:
        return False
    # local capacity must be a positive multiple of 4
    t_loc = (b // dsz) * (s // msz)
    return t_loc * cfg.top_k >= cfg.n_experts


def _local_dispatch(cfg: ModelConfig, x, router):
    """x (T,D) local tokens -> (xe (E,C,D), combine (E*C,), dispatch
    (E*C,) token ids, aux)."""
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = max(4, -(-int(t * k * cfg.capacity_factor / e) // 4) * 4)
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, k)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    density = jnp.mean(jax.nn.one_hot(top_ids[..., 0], e), axis=0)
    aux = jnp.sum(density * jnp.mean(probs, axis=0)) * e

    flat_ids = top_ids.reshape(t * k)
    flat_w = top_w.reshape(t * k)
    onehot = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(t * k), flat_ids]
    keep = pos < cap
    slot = jnp.where(keep, flat_ids * cap + pos, e * cap)
    token_of = (jnp.arange(t * k) // k).astype(jnp.int32)
    dispatch = jnp.full((e * cap + 1,), t, jnp.int32)
    combine = jnp.zeros((e * cap + 1,), jnp.float32)
    dispatch = dispatch.at[slot].set(token_of, mode="drop")
    combine = combine.at[slot].set(flat_w, mode="drop")
    dispatch, combine = dispatch[:-1], combine[:-1]
    xpad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    xe = jnp.take(xpad, dispatch, axis=0).reshape(e, cap, d)
    return xe, combine, dispatch, aux, cap


def moe_block_ep(p: Dict[str, Any], cfg: ModelConfig,
                 x: jnp.ndarray,
                 n_valid=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in replacement for lm.moe_block when ep_applicable().

    The masked dispatch (``n_valid``, serving's bucketed-MoE prefill —
    see ``lm.moe_dispatch``) is NOT supported here:
    ``_local_dispatch`` computes per-shard queue
    positions over locally contiguous token ranges, and a right-padded
    bucket would scatter real tokens across shards differently than
    the unpadded run.  ``lm.moe_block`` therefore keeps masked calls
    on the single-device path; this guard is the backstop."""
    if n_valid is not None:
        raise NotImplementedError(
            "masked MoE dispatch is single-device only "
            "(lm.moe_block routes it off the EP path)")
    ctx = _act_ctx()
    mesh = ctx.mesh
    msz = mesh.shape["model"]
    batch_axes = ctx.batch_axes
    b, s, d = x.shape
    e = cfg.n_experts
    e_loc = e // msz
    gated = cfg.act in ("silu", "geglu")

    def gate_fn(g):
        return jax.nn.silu(g) if cfg.act == "silu" else jax.nn.gelu(g)

    has_wg = "wg" in p["experts"]

    def local(x_loc, router, wi, wg, wo):
        # x_loc (B_loc, S_loc, D); expert weights arrive sharded E over
        # model and D over data -> gather D (the FSDP all-gather)
        for ax in reversed(batch_axes):
            wi = jax.lax.all_gather(wi, ax, axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, ax, axis=2, tiled=True)
            if has_wg:
                wg = jax.lax.all_gather(wg, ax, axis=1, tiled=True)
        bl, sl, _ = x_loc.shape
        xt = x_loc.reshape(bl * sl, d)
        xe, combine, dispatch, aux, cap = _local_dispatch(cfg, xt, router)
        # ---- all-to-all: experts to their owning shard --------------
        # (E, C, D) -> (E_loc, msz*C, D)
        xe = jax.lax.all_to_all(xe, "model", split_axis=0, concat_axis=1,
                                tiled=True)
        hid = jnp.einsum("ecd,edf->ecf", xe, wi)
        if gated:
            hid = gate_fn(jnp.einsum("ecd,edf->ecf", xe, wg)) * hid
        else:
            hid = jax.nn.gelu(hid)
        ye = jnp.einsum("ecf,efd->ecd", hid, wo)
        # ---- all-to-all back: (E_loc, msz*C, D) -> (E, C, D) ---------
        ye = jax.lax.all_to_all(ye, "model", split_axis=1, concat_axis=0,
                                tiled=True)
        ye = ye.reshape(e * cap, d) * combine[:, None].astype(ye.dtype)
        ypad = jnp.zeros((bl * sl + 1, d), ye.dtype)
        y = ypad.at[dispatch].add(ye)[:-1]
        aux = jax.lax.pmean(aux, ("model",) + tuple(batch_axes))
        return y.reshape(bl, sl, d), aux

    dm = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    w_spec = P("model", dm, None)
    wo_spec = P("model", None, dm)
    wg_arg = p["experts"]["wg"] if has_wg \
        else jnp.zeros_like(p["experts"]["wi"])
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(batch_axes, "model", None), P(None, None),
                  w_spec, w_spec, wo_spec),
        out_specs=(P(batch_axes, "model", None), P()),
        check_vma=False)
    y, aux = fn(x, p["router"], p["experts"]["wi"], wg_arg,
                p["experts"]["wo"])

    if cfg.n_shared_experts:
        from repro.models.lm import GATED_ACTS, _gate
        sh = p["shared"]
        hid = jnp.einsum("bsd,df->bsf", x, sh["wi"])
        if cfg.act in GATED_ACTS:
            hid = _gate(cfg.act, jnp.einsum("bsd,df->bsf", x, sh["wg"])) \
                * hid
        else:
            hid = jax.nn.gelu(hid)
        y = y + jnp.einsum("bsf,fd->bsd", hid, sh["wo"])
    return y, aux
