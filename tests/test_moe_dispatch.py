"""Property tests on the MoE capacity-dispatch invariants (hypothesis).

Invariants:
  * every kept (token, k) pair lands in the queue slot of the expert it
    was routed to, at a position < capacity;
  * no expert receives more than `capacity` tokens;
  * combine weights are the normalized top-k router probabilities for
    kept slots and 0 for dropped/dummy slots;
  * with a dropless capacity factor nothing is dropped and the block
    output equals the dense mixture of the same experts.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models import get_model, lm
from repro.models.common import ModelConfig
from repro.models.lm import moe_block, moe_capacity, moe_dispatch


def _cfg(e, k, cf):
    base = get_config("qwen3-moe-30b-a3b", reduced=True)
    return dataclasses.replace(base, n_experts=e, top_k=k,
                               capacity_factor=cf)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([4, 8]),
       st.sampled_from([1, 2]), st.integers(8, 64))
def test_dispatch_invariants(seed, e, k, t):
    cfg = _cfg(e, k, 1.25)
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(0, 1, (1, t, e)), jnp.float32)
    cap = moe_capacity(cfg, t, capped=True)
    dispatch, combine, aux = jax.jit(
        lambda l: moe_dispatch(l, cfg, cap))(logits)
    dispatch = np.asarray(dispatch)[0]          # (E*C,)
    combine = np.asarray(combine)[0]
    assert dispatch.shape == (e * cap,)
    # capacity respected: each expert's queue has exactly `cap` slots
    per_expert = dispatch.reshape(e, cap)
    for ei in range(e):
        kept = per_expert[ei][per_expert[ei] < t]
        assert len(kept) <= cap
        # every kept token actually routed to this expert (top-k)
        probs = np.asarray(jax.nn.softmax(logits[0], axis=-1))
        for tok in kept:
            topk = np.argsort(probs[tok])[-k:]
            assert ei in topk, (ei, tok, topk)
    # dummy slots have zero combine weight
    assert (combine[dispatch == t] == 0).all()
    # kept combine weights are positive and <= 1
    kept_w = combine[dispatch < t]
    assert (kept_w >= 0).all() and (kept_w <= 1 + 1e-6).all()
    assert np.isfinite(float(aux)) and float(aux) > 0


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([4, 8]),
       st.sampled_from([1, 2]), st.sampled_from([16, 32]),
       st.data())
def test_capacity_stable_padding_never_leaks(seed, e, k, bucket, data):
    """Bucketed dispatch (serving's bucketed-MoE prefill, dropless):
    for a random true length m within a bucket, the masked dispatch
    over the PADDED tokens (capacity the bucket's tokens, ``n_valid``
    m) must (a) never dispatch a padded token to any expert and (b)
    dispatch exactly the same (expert, queue-position, token, weight)
    set as the unpadded run — so the downstream expert FFN + combine is
    bit-identical."""
    m = data.draw(st.integers(2, bucket - 1))
    cfg = _cfg(e, k, 1.25)
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(0, 1, (1, bucket, e)), jnp.float32)
    cap_pad = moe_capacity(cfg, bucket)
    cap_m = moe_capacity(cfg, m)
    d_pad, c_pad, _ = jax.jit(lambda l: moe_dispatch(
        l, cfg, cap_pad, n_valid=jnp.int32(m)))(logits)
    d_ref, c_ref, _ = jax.jit(lambda l: moe_dispatch(
        l, cfg, cap_m))(logits[:, :m])
    d_pad = np.asarray(d_pad)[0].reshape(e, cap_pad)
    c_pad = np.asarray(c_pad)[0].reshape(e, cap_pad)
    d_ref = np.asarray(d_ref)[0].reshape(e, cap_m)
    c_ref = np.asarray(c_ref)[0].reshape(e, cap_m)
    # (a) padded tokens never leak into any expert queue (dummy slots
    # carry the out-of-range sentinel: `bucket` here, `m` in the ref)
    kept = d_pad[d_pad < bucket]
    assert (kept < m).all(), kept
    # beyond the true length's capacity every slot is a dummy
    assert (d_pad[:, cap_m:] == bucket).all()
    assert (c_pad[:, cap_m:] == 0).all()
    # (b) the kept prefix of each expert queue matches the unpadded
    # dispatch slot for slot — token ids and combine weights
    ref_tok = np.where(d_ref < m, d_ref, -1)
    pad_tok = np.where(d_pad[:, :cap_m] < bucket, d_pad[:, :cap_m], -1)
    np.testing.assert_array_equal(pad_tok, ref_tok)
    np.testing.assert_array_equal(c_pad[:, :cap_m], c_ref)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.data())
def test_capacity_stable_block_output_bit_identical(seed, data):
    """End to end through moe_block: the masked run over padded tokens
    emits BIT-IDENTICAL outputs for the real rows — same expert set,
    same queue positions, same expert-major combine order, so even the
    float summation order is preserved."""
    from repro.models.lm import moe_block
    m = data.draw(st.integers(2, 15))
    bucket = 16
    cfg = _cfg(4, 2, 1.25)
    key = jax.random.PRNGKey(seed % (2**31 - 1))
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    p = {"router": jax.random.normal(key, (d, e), jnp.float32) * 0.1,
         "experts": {
             "wi": jax.random.normal(key, (e, d, f)) * 0.05,
             "wg": jax.random.normal(jax.random.fold_in(key, 1),
                                     (e, d, f)) * 0.05,
             "wo": jax.random.normal(jax.random.fold_in(key, 2),
                                     (e, f, d)) * 0.05}}
    x = jax.random.normal(jax.random.fold_in(key, 3), (1, bucket, d))
    y_pad, _ = jax.jit(lambda p, x: moe_block(
        p, cfg, x, data_shards=1, n_valid=jnp.int32(m)))(p, x)
    y_ref, _ = jax.jit(lambda p, x: moe_block(
        p, cfg, x, data_shards=1))(p, x[:, :m])
    np.testing.assert_array_equal(np.asarray(y_pad)[:, :m],
                                  np.asarray(y_ref))


def test_dropless_matches_dense_mixture():
    """capacity_factor high enough -> block output == explicit dense
    top-k mixture computed with plain numpy-style einsums."""
    cfg = _cfg(4, 2, 16.0)
    key = jax.random.PRNGKey(0)
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    p = {"router": jax.random.normal(key, (d, e), jnp.float32) * 0.1,
         "experts": {
             "wi": jax.random.normal(key, (e, d, f)) * 0.05,
             "wg": jax.random.normal(jax.random.fold_in(key, 1),
                                     (e, d, f)) * 0.05,
             "wo": jax.random.normal(jax.random.fold_in(key, 2),
                                     (e, f, d)) * 0.05}}
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, 8, d))
    from repro.models.lm import moe_block
    y, aux = jax.jit(lambda p, x: moe_block(p, cfg, x, data_shards=1,
                                            capped=True))(p, x)

    # dense reference: run every expert on every token, weight by the
    # renormalized top-k probabilities
    xt = x.reshape(-1, d)
    probs = jax.nn.softmax(xt.astype(jnp.float32) @ p["router"], -1)
    top_w, top_ids = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    hid = jnp.einsum("td,edf->tef", xt, p["experts"]["wi"])
    gate = jnp.einsum("td,edf->tef", xt, p["experts"]["wg"])
    out_e = jnp.einsum("tef,efd->ted", jax.nn.silu(gate) * hid,
                       p["experts"]["wo"])
    mask = jax.nn.one_hot(top_ids, e).sum(1)        # (T,E) 0/1
    w_full = jnp.zeros_like(probs)
    for kk in range(cfg.top_k):
        w_full = w_full + jax.nn.one_hot(top_ids[:, kk], e) \
            * top_w[:, kk:kk + 1]
    want = jnp.einsum("te,ted->td", w_full, out_e).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-3)


def test_unnormalised_combine_weights_are_the_softmax_probabilities():
    """``norm_topk_prob`` false (DeepSeekMoE as published): every kept
    slot's combine weight is its token's raw softmax probability of
    that expert, so a token's weights sum to less than 1."""
    cfg = dataclasses.replace(_cfg(8, 3, 8.0), norm_topk_prob=False)
    rng = np.random.default_rng(5)
    t = 12
    logits = jnp.asarray(rng.normal(0, 1, (1, t, 8)), jnp.float32)
    cap = moe_capacity(cfg, t)
    dispatch, combine, _ = moe_dispatch(logits, cfg, cap)
    probs = np.asarray(jax.nn.softmax(logits[0], axis=-1))
    dispatch = np.asarray(dispatch)[0].reshape(8, cap)
    combine = np.asarray(combine)[0].reshape(8, cap)
    per_token = np.zeros(t)
    for e in range(8):
        for c in range(cap):
            tok = dispatch[e, c]
            if tok < t:
                assert combine[e, c] == probs[tok, e]
                per_token[tok] += combine[e, c]
    want = np.sort(probs, axis=-1)[:, -3:].sum(-1)
    np.testing.assert_allclose(per_token, want, rtol=1e-6)
    assert (per_token < 1.0).all()


def _crowded_decode(cfg, params, slot_tokens):
    """Decode logits of 16 slots at position 9 of an empty cache."""
    model = get_model(cfg)
    cache = model.empty_cache(16, 32, cfg.jnp_dtype())
    lengths = jnp.full((16,), 9, jnp.int32)
    logits, _ = jax.jit(lambda p, c, t: lm.lm_decode(p, cfg, c, t, lengths)
                        )(params, cache, jnp.asarray(slot_tokens)[:, None])
    return np.asarray(logits)


def test_dropless_decode_slot_ignores_what_the_other_slots_route():
    """Serving never drops a token: the last slot's decode logits are
    bit-identical whether the other 15 slots hold an idle token or the
    last slot's own token (so all 16 route to its experts).  At
    ``capacity_factor`` 1.25 an expert holds 12 of the 16, and the
    capacity dispatch drops the last slot's rows: the block's output
    for that slot then differs, which the check below confirms."""
    cfg = dataclasses.replace(get_config("deepseek-moe-16b", reduced=True),
                              capacity_factor=1.25)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    tok = 17
    alone = _crowded_decode(cfg, params, [0] * 15 + [tok])
    crowded = _crowded_decode(cfg, params, [tok] * 16)
    np.testing.assert_array_equal(alone[15], crowded[15])
    # the same crowd through the training (capacity) dispatch drops it
    p = jax.tree.map(lambda a: a[0], params["blocks"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 1, cfg.d_model))
    xs = jnp.concatenate([jnp.zeros((15, 1, cfg.d_model)), x]), \
        jnp.concatenate([x] * 16)
    y = [np.asarray(moe_block(p, cfg, v.reshape(1, 16, -1), data_shards=1,
                              capped=True)[0])[0, 15] for v in xs]
    assert not np.array_equal(y[0], y[1])
    y = [np.asarray(moe_block(p, cfg, v.reshape(1, 16, -1),
                              data_shards=1)[0])[0, 15] for v in xs]
    np.testing.assert_array_equal(y[0], y[1])


def test_sharded_moe_engine_decodes_as_one_device():
    """On 4 forced host devices the mesh-sharded moe engine (experts
    and heads over ``model``) decodes the same logits as the one-device
    engine, at float32 rounding: the sharded path sums the experts'
    and heads' parts across devices in another order."""
    script = textwrap.dedent("""
        import jax, numpy as np
        from repro.configs import get_config
        from repro.launch.mesh import make_serving_mesh
        from repro.models import get_model
        from repro.serving import Request, ServingEngine

        cfg = get_config("deepseek-moe-16b", reduced=True)
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(4))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab - 1, n).astype(np.int32)
                   for n in (7, 19, 12, 30)]
        out = []
        for mesh in (None, make_serving_mesh(4)):
            eng = ServingEngine(model, params, max_slots=4, cache_len=64,
                                mesh=mesh)
            seen = []
            inner = eng._decode
            def record(inputs, inner=inner, seen=seen):
                logits, kv = inner(inputs)
                seen.append(np.asarray(logits))
                return logits, kv
            eng._decode = record
            for uid, p in enumerate(prompts):
                eng.submit(Request(uid=uid, tokens=p, max_new_tokens=5))
            eng.run()
            out.append(np.stack(seen))
        one, four = out
        scale = float(np.abs(one).max())
        print(one.shape == four.shape, float(np.abs(one - four).max()) / scale)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    same_shape, rel = out.stdout.split()
    assert same_shape == "True"
    assert float(rel) < 1e-4
