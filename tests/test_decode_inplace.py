"""The contiguous decode step writes each layer's new K/V rows in place
on the stacked cache, and the engine donates that cache into the step.

``lm_decode`` is checked bit for bit — logits and the whole cache —
against the one-hot ring update it replaced, kept here as the
reference: dense (f32 and bf16), moe (its dense ``first_block`` writes
layer 0 of the same buffer) and vlm, with the reference attention and
the Pallas kernel in interpret mode, over slots at different lengths,
an idle slot and a ring that has wrapped.  The engine tests check that
a step consumes the KV it was handed (ring and paged pool) while a
plain call of the step leaves its inputs valid, with one program, and
that on a mesh of four host devices the step keeps the KV's very
buffers."""

import dataclasses
import math
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import jit_cache_size
from repro.kernels import ops
from repro.models import get_model, lm
from repro.models.common import rms_norm
from repro.serving import Request, ServingEngine

C = 16
# five slots: different lengths, an idle slot left at 0, a full ring
# (length C writes position 0 again) and a ring wrapped twice
LENGTHS = [3, 9, 0, C, 2 * C + 5]


def onehot_decode(params, cfg, cache, tokens, lengths, embed_scale=None,
                  attn_impl=None):
    """The decode step as it was: each layer rebuilds its ring with a
    one-hot multiply-add, the scan re-stacks the layers' rings, and
    the moe ``first_block``'s ring is concatenated in front."""
    x = lm.embed_tokens(params, cfg, tokens)
    if embed_scale is not None:
        x = x * jnp.asarray(embed_scale, x.dtype)

    def layer(h, p_l, ck, cv):
        xin = rms_norm(h, p_l["ln1"], cfg.norm_eps)
        att, ck, cv = lm.decode_attention_block(p_l["attn"], cfg, xin, ck,
                                                cv, lengths, attn_impl)
        hh = h + att
        hin = rms_norm(hh, p_l["ln2"], cfg.norm_eps)
        if "moe" in p_l:
            return hh + lm.moe_block(p_l["moe"], cfg, hin, 16)[0], ck, cv
        return hh + lm.mlp_block(p_l["mlp"], cfg, hin), ck, cv

    first = []
    if "first_block" in params:
        fb = jax.tree.map(lambda a: a[0], params["first_block"])
        x, k0, v0 = layer(x, fb, cache["k"][0], cache["v"][0])
        first = [(k0, v0)]

    def body(h, xs):
        h, ck, cv = layer(h, *xs)
        return h, (ck, cv)

    i0 = len(first)
    x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"],
                                         cache["k"][i0:], cache["v"][i0:]))
    if first:
        ks = jnp.concatenate([first[0][0][None], ks])
        vs = jnp.concatenate([first[0][1][None], vs])
    return lm.lm_logits(params, cfg, x)[:, 0], {"k": ks, "v": vs}


def _setup(arch, dtype=None):
    cfg = get_config(arch, reduced=True)
    if dtype:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = len(LENGTHS)
    rng = np.random.default_rng(1)
    shape = (cfg.n_layers, b, cfg.n_kv_heads, C, cfg.dh)
    cache = {kk: jnp.asarray(rng.normal(size=shape), cfg.jnp_dtype())
             for kk in ("k", "v")}
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (b, 1)), jnp.int32)
    return cfg, params, cache, tokens, jnp.asarray(LENGTHS, jnp.int32)


@pytest.mark.parametrize("arch,dtype", [
    ("phi4-mini-3.8b", None), ("phi4-mini-3.8b", "bfloat16"),
    ("deepseek-moe-16b", None), ("paligemma-3b", None)],
    ids=["dense", "dense-bf16", "moe", "vlm"])
@pytest.mark.parametrize("kernel", [False, True],
                         ids=["reference", "pallas"])
def test_in_place_decode_matches_onehot_bit_for_bit(arch, dtype, kernel):
    cfg, params, cache, tokens, lengths = _setup(arch, dtype)
    scale = math.sqrt(cfg.d_model) if cfg.family == "vlm" else None
    attn = ops.decode_attention if kernel else None
    got = jax.jit(lambda c: lm.lm_decode(params, cfg, c, tokens, lengths,
                                         embed_scale=scale,
                                         attn_impl=attn))(cache)
    want = jax.jit(lambda c: onehot_decode(params, cfg, c, tokens, lengths,
                                           scale, attn))(cache)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for kk in ("k", "v"):
        assert got[1][kk].dtype == cache[kk].dtype
        np.testing.assert_array_equal(np.asarray(got[1][kk]),
                                      np.asarray(want[1][kk]))
    # the new rows landed at lengths % C of every layer, idle slot too
    new = np.asarray(got[1]["k"])
    old = np.asarray(cache["k"])
    for b, n in enumerate(LENGTHS):
        keep = np.arange(C) != n % C
        np.testing.assert_array_equal(new[:, b][:, :, keep],
                                      old[:, b][:, :, keep])
        assert not np.array_equal(new[:, b, :, n % C], old[:, b, :, n % C])


@pytest.fixture(scope="module")
def dense():
    cfg = get_config("phi4-mini-3.8b", reduced=True)
    model = get_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _kv(eng):
    return eng.kv_pool if eng.paged else eng.cache


@pytest.mark.parametrize("kv_block", [0, 16], ids=["ring", "paged"])
def test_engine_step_consumes_its_cache(dense, kv_block):
    model, params = dense
    eng = ServingEngine(model, params, max_slots=2, cache_len=64,
                        kv_block=kv_block or None)
    eng.submit(Request(uid=0, tokens=np.arange(5, 17, dtype=np.int32),
                       max_new_tokens=6))
    eng.step()                       # admits and runs the first decode
    before = jax.tree.leaves(_kv(eng))
    eng.step()
    assert all(a.is_deleted() for a in before)
    assert not any(a.is_deleted() for a in jax.tree.leaves(_kv(eng)))
    eng.run()
    assert len(eng.results[0].output) == 6
    assert jit_cache_size(eng._decode) == 1


def test_plain_call_keeps_its_inputs_and_the_program(dense):
    """``engine._decode(inputs)`` runs the engine's one program on a copy
    of the KV: the inputs stay valid for a replay, which gives the same
    logits, while ``consume`` deletes the KV it was handed."""
    model, params = dense
    eng = ServingEngine(model, params, max_slots=2, cache_len=64)
    inputs = (eng.params, eng.cache, eng.cur_tokens, eng.lengths)
    first, kv = eng._decode(inputs)
    again, _ = eng._decode(inputs)
    assert not any(a.is_deleted() for a in jax.tree.leaves(inputs[1]))
    np.testing.assert_array_equal(np.asarray(first), np.asarray(again))
    eng._decode.consume((eng.params, kv, eng.cur_tokens, eng.lengths))
    assert all(a.is_deleted() for a in jax.tree.leaves(kv))
    assert jit_cache_size(eng._decode) == 1


def test_sharded_step_keeps_its_kv_buffers():
    """On a 4-device mesh a decode step hands the KV back on the
    placement it came in on, so the donated buffers are reused and the
    engine's re-pin copies nothing.  The reduced dense model has 2 KV
    heads: without the step's own constraint GSPMD returned the paged
    pool replicated, and every step copied it."""
    script = textwrap.dedent("""
        import jax, numpy as np
        from repro.configs import get_config
        from repro.launch.mesh import make_serving_mesh
        from repro.models import get_model
        from repro.serving import Request, ServingEngine

        def buffers(tree):
            return [[s.data.unsafe_buffer_pointer()
                     for s in leaf.addressable_shards]
                    for leaf in jax.tree.leaves(tree)]

        cfg = get_config("phi4-mini-3.8b", reduced=True)
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        for kv_block in (None, 16):
            eng = ServingEngine(model, params, max_slots=2, cache_len=64,
                                kv_block=kv_block,
                                mesh=make_serving_mesh(4))
            eng.submit(Request(uid=0, tokens=np.arange(5, 17, dtype=np.int32),
                               max_new_tokens=4))
            eng.step()
            kv = eng.kv_pool if eng.paged else eng.cache
            before = buffers(kv)
            eng.step()
            after = buffers(eng.kv_pool if eng.paged else eng.cache)
            print(kv_block, all(a.is_deleted() for a in jax.tree.leaves(kv)),
                  after == before)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.split() == ["None", "True", "True",
                                  "16", "True", "True"]


def test_sharded_engine_makes_its_kv_in_place():
    """On a mesh the engine makes its KV (ring or paged pool) straight
    into its sharding: each device makes only its shard.  A KV made
    whole on one device first, then sharded, put deepseek-moe-16b's
    7.5 GB ring beside chip 0's 8.2 GB of weights and ran out of
    memory on four v5e."""
    script = textwrap.dedent("""
        import dataclasses, jax, numpy as np
        from repro.configs import get_config
        from repro.launch.mesh import make_serving_mesh
        from repro.models import get_model
        from repro.serving import ServingEngine

        cfg = get_config("deepseek-moe-16b", reduced=True)
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        for kv_block in (None, 16):
            made = []

            def empty_cache(batch, length, dtype):
                tree = model.empty_cache(batch, length, dtype)
                made.append(isinstance(tree["k"], jax.core.Tracer))
                return tree

            spy = dataclasses.replace(model, empty_cache=empty_cache)
            eng = ServingEngine(spy, params, max_slots=4, cache_len=64,
                                kv_block=kv_block,
                                mesh=make_serving_mesh(4))
            kv = eng.kv_pool if eng.paged else eng.cache
            quarter = all(s.data.nbytes * 4 == leaf.nbytes
                          for leaf in jax.tree.leaves(kv)
                          for s in leaf.addressable_shards)
            print(kv_block, made == [True] * len(made), quarter)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.split() == ["None", "True", "True",
                                  "16", "True", "True"]
