"""Model registry: one uniform bundle per architecture family.

Every assigned architecture resolves to a ``ModelBundle`` exposing:

  init(key) -> params
  loss(params, batch) -> (loss, metrics)              [train_4k]
  prefill(params, batch, cache_len, window) -> (logits, cache)
  decode(params, cache, tokens, lengths, window) -> (logits, cache)
  empty_cache(batch, cache_len, dtype) -> cache pytree
  batch_shapes(mode, batch, seq) -> {name: ShapeDtypeStruct}

``batch_shapes`` is the dry-run contract: weak-type-correct stand-ins
for every model input, no allocation (MULTI-POD DRY-RUN step 2).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import encdec, hybrid, lm, ssm, vlm
from .common import ModelConfig

SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ModelConfig
    init: Callable
    loss: Callable
    prefill: Callable
    decode: Callable
    empty_cache: Callable
    batch_shapes: Callable

    def make_batch(self, rng: np.random.Generator, mode: str, batch: int,
                   seq: int) -> Dict[str, jnp.ndarray]:
        """Concrete random inputs matching batch_shapes (smoke tests)."""
        out = {}
        for name, s in self.batch_shapes(mode, batch, seq).items():
            if jnp.issubdtype(s.dtype, jnp.integer):
                if name == "lengths":
                    arr = rng.integers(1, seq, s.shape)
                else:
                    arr = rng.integers(0, self.cfg.vocab, s.shape)
            else:
                arr = rng.normal(0, 1, s.shape)
            out[name] = jnp.asarray(arr, s.dtype)
        return out


def _tok_shapes(cfg, mode, batch, seq):
    if mode == "train":
        return {"tokens": SDS((batch, seq), jnp.int32),
                "labels": SDS((batch, seq), jnp.int32)}
    if mode == "prefill":
        return {"tokens": SDS((batch, seq), jnp.int32)}
    return {"tokens": SDS((batch, 1), jnp.int32),
            "lengths": SDS((batch,), jnp.int32)}


# ---------------------------------------------------------------------------
# per-family bundles
# ---------------------------------------------------------------------------

def _dense_bundle(cfg: ModelConfig) -> ModelBundle:
    def prefill(params, batch, cache_len=None, window=None,
                data_shards=16):
        # n_valid: the true token count of a bucketed-MoE prefill,
        # which the serving engine puts in the batch (a traced value,
        # see lm.moe_dispatch); absent for exact-length/non-moe prefill
        return lm.lm_prefill(params, cfg, batch["tokens"], cache_len,
                             window=window, data_shards=data_shards,
                             n_valid=batch.get("n_valid"))

    def decode(params, cache, tokens, lengths, window=None,
               data_shards=16):
        return lm.lm_decode(params, cfg, cache, tokens, lengths,
                            data_shards=data_shards)

    def empty_cache(batch, cache_len, dtype):
        L = cfg.n_layers
        return {"k": jnp.zeros((L, batch, cfg.n_kv_heads, cache_len,
                                cfg.dh), dtype),
                "v": jnp.zeros((L, batch, cfg.n_kv_heads, cache_len,
                                cfg.dh), dtype)}

    return ModelBundle(
        cfg=cfg,
        init=lambda key: lm.init_lm(key, cfg),
        loss=lambda params, batch, **kw: lm.lm_loss(params, cfg, batch,
                                                    **kw),
        prefill=prefill, decode=decode, empty_cache=empty_cache,
        batch_shapes=lambda mode, b, s: _tok_shapes(cfg, mode, b, s))


def _ssm_bundle(cfg: ModelConfig) -> ModelBundle:
    def empty_cache(batch, cache_len, dtype):
        return ssm.ssm_empty_cache(cfg, batch, dtype)

    return ModelBundle(
        cfg=cfg,
        init=lambda key: ssm.init_ssm_lm(key, cfg),
        loss=lambda params, batch, **kw: ssm.ssm_loss(params, cfg, batch,
                                                      **kw),
        prefill=lambda params, batch, cache_len=None, window=None, **kw:
            ssm.ssm_prefill(params, cfg, batch["tokens"], cache_len),
        decode=lambda params, cache, tokens, lengths, window=None, **kw:
            ssm.ssm_decode(params, cfg, cache, tokens, lengths),
        empty_cache=empty_cache,
        batch_shapes=lambda mode, b, s: _tok_shapes(cfg, mode, b, s))


def _hybrid_bundle(cfg: ModelConfig) -> ModelBundle:
    def empty_cache(batch, cache_len, dtype):
        return hybrid.hybrid_empty_cache(cfg, batch, cache_len, dtype)

    return ModelBundle(
        cfg=cfg,
        init=lambda key: hybrid.init_hybrid_lm(key, cfg),
        loss=lambda params, batch, **kw: hybrid.hybrid_loss(
            params, cfg, batch, **kw),
        prefill=lambda params, batch, cache_len=None, window=None, **kw:
            hybrid.hybrid_prefill(params, cfg, batch["tokens"], cache_len,
                                  window=window),
        decode=lambda params, cache, tokens, lengths, window=None, **kw:
            hybrid.hybrid_decode(params, cfg, cache, tokens, lengths,
                                 window=window),
        empty_cache=empty_cache,
        batch_shapes=lambda mode, b, s: _tok_shapes(cfg, mode, b, s))


def _vlm_bundle(cfg: ModelConfig) -> ModelBundle:
    p, dv = cfg.n_vision_tokens, cfg.d_vision

    def batch_shapes(mode, b, s):
        base = _tok_shapes(cfg, mode, b, max(s - p, 1))
        if mode in ("train", "prefill"):
            base["vision"] = SDS((b, p, dv), cfg.jnp_dtype())
        return base

    def empty_cache(batch, cache_len, dtype):
        L = cfg.n_layers
        return {"k": jnp.zeros((L, batch, cfg.n_kv_heads, cache_len,
                                cfg.dh), dtype),
                "v": jnp.zeros((L, batch, cfg.n_kv_heads, cache_len,
                                cfg.dh), dtype)}

    return ModelBundle(
        cfg=cfg,
        init=lambda key: vlm.init_vlm(key, cfg),
        loss=lambda params, batch, **kw: vlm.vlm_loss(params, cfg, batch,
                                                      **kw),
        prefill=lambda params, batch, cache_len=None, window=None, **kw:
            vlm.vlm_prefill(params, cfg, batch, cache_len, window=window),
        decode=lambda params, cache, tokens, lengths, window=None, **kw:
            vlm.vlm_decode(params, cfg, cache, tokens, lengths),
        empty_cache=empty_cache, batch_shapes=batch_shapes)


def _audio_bundle(cfg: ModelConfig) -> ModelBundle:
    t = cfg.n_audio_ctx

    def batch_shapes(mode, b, s):
        base = _tok_shapes(cfg, mode, b, s)
        if mode in ("train", "prefill"):
            base["frames"] = SDS((b, t, cfg.d_model), cfg.jnp_dtype())
        return base

    def empty_cache(batch, cache_len, dtype):
        L, kh, dh = cfg.n_layers, cfg.n_kv_heads, cfg.dh
        return {"k": jnp.zeros((L, batch, kh, cache_len, dh), dtype),
                "v": jnp.zeros((L, batch, kh, cache_len, dh), dtype),
                "cross_k": jnp.zeros((L, batch, kh, t, dh), dtype),
                "cross_v": jnp.zeros((L, batch, kh, t, dh), dtype)}

    return ModelBundle(
        cfg=cfg,
        init=lambda key: encdec.init_encdec(key, cfg),
        loss=lambda params, batch, **kw: encdec.encdec_loss(
            params, cfg, batch, **kw),
        prefill=lambda params, batch, cache_len=None, window=None, **kw:
            encdec.encdec_prefill(params, cfg, batch, cache_len,
                                  window=window),
        decode=lambda params, cache, tokens, lengths, window=None, **kw:
            encdec.encdec_decode(params, cfg, cache, tokens, lengths),
        empty_cache=empty_cache, batch_shapes=batch_shapes)


_BUILDERS = {
    "dense": _dense_bundle,
    "moe": _dense_bundle,       # MoE shares the lm.py code path
    "ssm": _ssm_bundle,
    "hybrid": _hybrid_bundle,
    "vlm": _vlm_bundle,
    "audio": _audio_bundle,
}


def get_model(cfg: ModelConfig) -> ModelBundle:
    return _BUILDERS[cfg.family](cfg)
