"""Quantized pod serving (docs/QUANTIZATION.md): the SERVING_*_Q tag
chain, the int8/int4 weight layout, and the int8 per-head-scale KV
cache.

Three layers of gate:

  * **primitive properties** — int4 pack/unpack round-trips exactly
    for every value in [-8, 7] (jnp and the np export twins agree),
    and the per-head KV scale quantization bounds each element's
    error by half a quantization step (all-zero vectors exact).
    Following tests/test_streaming.py, hypothesis sweeps engage when
    installed; seeded deterministic sweeps cover the same properties
    either way.
  * **accuracy** — a quantized engine's logits track the fp engine
    within the DOCUMENTED per-family tolerance
    (benchmarks/quantized_decode.py carries the same table).
    Quantized serving is tolerance-gated, never bit-gated, against
    fp: rounding weight values is a semantics change, deliberately.
  * **self-identity** — what IS bit-gated: a quantized engine against
    itself across admit/preempt/restore (the compile-once contract's
    quantized leg, ``jit_cache_size == 1`` throughout), and the paged
    quantized engine against the contiguous one (paging stays a
    layout change under quantization).

Families outside the quantized matrix refuse with the same typed
errors as every other fast path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.executor import jit_cache_size
from repro.core.quantize import (dequantize_kv_heads, pack_int4,
                                 pack_int4_np, quantize_kv_heads,
                                 unpack_int4, unpack_int4_np)
from repro.models import get_model
from repro.serving import Request, ServingEngine, UnsupportedFamilyError

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAS_HYPOTHESIS = True
except ImportError:                                  # pragma: no cover
    HAS_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAS_HYPOTHESIS, reason="hypothesis not installed")

ARCHS = {"dense": "qwen3-32b", "moe": "deepseek-moe-16b",
         "vlm": "paligemma-3b", "ssm": "mamba2-780m",
         "hybrid": "zamba2-1.2b", "audio": "whisper-large-v3"}
CACHE_LEN = 32
PROMPT_LEN = 6
N_NEW = 6
# documented max-abs logit tolerance vs the fp engine — the same
# numbers benchmarks/quantized_decode.py asserts (moe loosest: weight
# rounding can flip discrete expert routing; vlm amplifies embedding
# error through its sqrt(d_model) scale)
TOLERANCE = {
    "dense": {"int8": 0.5, "int4": 2.0},
    "moe": {"int8": 2.5, "int4": 4.0},
    "vlm": {"int8": 1.5, "int4": 4.0},
}

_SETUP = {}


def _setup(family):
    if family not in _SETUP:
        cfg = get_config(ARCHS[family], reduced=True)
        m = get_model(cfg)
        _SETUP[family] = (cfg, m, m.init(jax.random.PRNGKey(0)))
    return _SETUP[family]


# ---------------------------------------------------------------------------
# primitive properties: int4 packing
# ---------------------------------------------------------------------------

def _assert_int4_roundtrip(q):
    packed = pack_int4(q)
    assert packed.shape == (*q.shape[:-1], q.shape[-1] // 2)
    assert packed.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)), q)
    # np export twins agree with the jnp pair byte-for-byte
    packed_np = pack_int4_np(q)
    np.testing.assert_array_equal(np.asarray(packed), packed_np)
    np.testing.assert_array_equal(unpack_int4_np(packed_np), q)


def test_int4_roundtrip_deterministic():
    rng = np.random.default_rng(7)
    for shape in ((2,), (4, 6), (3, 2, 8), (1, 16)):
        q = rng.integers(-8, 8, shape).astype(np.int8)
        _assert_int4_roundtrip(q)
    # every representable value, in both nibble positions
    q = np.array([[v, w] for v in range(-8, 8)
                  for w in range(-8, 8)], np.int8)
    _assert_int4_roundtrip(q)


def test_int4_odd_last_axis_refused():
    with pytest.raises(ValueError, match="even last axis"):
        pack_int4(np.zeros((2, 3), np.int8))


if HAS_HYPOTHESIS:
    @needs_hypothesis
    @settings(max_examples=40, deadline=None)
    @given(vals=st.lists(st.integers(-8, 7), min_size=2, max_size=32),
           lead=st.integers(1, 3))
    def test_int4_roundtrip_hypothesis(vals, lead):
        vals = vals[:len(vals) // 2 * 2]
        q = np.tile(np.asarray(vals, np.int8), (lead, 1))
        _assert_int4_roundtrip(q)


# ---------------------------------------------------------------------------
# primitive properties: per-head KV scale quantization
# ---------------------------------------------------------------------------

def _assert_kv_quant_bound(x):
    q, scales = quantize_kv_heads(x)
    assert q.dtype == jnp.int8
    assert scales.shape == x.shape[:-1]
    dq = np.asarray(dequantize_kv_heads(q, scales))
    # symmetric rounding: each element is off by at most half a step
    bound = np.asarray(scales)[..., None] * 0.5 + 1e-6
    assert np.all(np.abs(dq - np.asarray(x, np.float32)) <= bound)


def test_kv_head_quant_deterministic():
    rng = np.random.default_rng(3)
    for shape in ((4,), (2, 3, 8), (2, 1, 2, 4, 16)):
        _assert_kv_quant_bound(rng.normal(0, 2, shape)
                               .astype(np.float32))
    # all-zero head vectors dequantize EXACTLY (scale 1.0, q 0) — an
    # empty quantized cache is still an empty cache
    z = np.zeros((2, 3, 8), np.float32)
    q, scales = quantize_kv_heads(z)
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_array_equal(np.asarray(scales), 1.0)
    np.testing.assert_array_equal(
        np.asarray(dequantize_kv_heads(q, scales)), 0.0)


if HAS_HYPOTHESIS:
    @needs_hypothesis
    @settings(max_examples=40, deadline=None)
    @given(vals=st.lists(
        st.floats(-1e4, 1e4, allow_nan=False, width=32),
        min_size=1, max_size=24),
        heads=st.integers(1, 4))
    def test_kv_head_quant_hypothesis(vals, heads):
        x = np.tile(np.asarray(vals, np.float32), (heads, 1))
        _assert_kv_quant_bound(x)


# ---------------------------------------------------------------------------
# engine-level helpers
# ---------------------------------------------------------------------------

def _engine(family, wd, kd, **kw):
    cfg, m, params = _setup(family)
    kw.setdefault("max_slots", 2)
    kw.setdefault("cache_len", CACHE_LEN)
    kw.setdefault("prefill_buckets", False)
    return ServingEngine(m, params, weight_dtype=wd, kv_dtype=kd, **kw)


def _vision(cfg, rng):
    return {"vision": rng.normal(
        0, 1, (cfg.n_vision_tokens, cfg.d_vision)).astype(np.float32)}


def _serve(family, wd, kd, *, evict=False, **kw):
    """Serve 4 seeded requests; optionally force a mid-run eviction.
    Returns ({uid: tokens}, engine)."""
    cfg, _, _ = _setup(family)
    eng = _engine(family, wd, kd, **kw)
    rng = np.random.default_rng(5)
    extras = _vision(cfg, rng) if cfg.family == "vlm" else None
    for uid in range(4):
        toks = rng.integers(0, cfg.vocab - 2,
                            PROMPT_LEN).astype(np.int32)
        eng.submit(Request(uid=uid, tokens=toks, max_new_tokens=N_NEW,
                           extras=extras))
    steps, more, evicted = 0, True, False
    while more:
        more = eng.step()
        steps += 1
        assert steps < 400, (family, wd, kd, "did not converge")
        if evict and not evicted and steps >= 3:
            victim = next((s for s in range(eng.max_slots)
                           if eng.active[s]), None)
            if victim is not None:
                eng._evict(victim)
                evicted = True
    assert not evict or evicted, (family, "nothing running to evict")
    return {u: list(eng.results[u].output) for u in range(4)}, eng


def _logit_err(family, wd, kd, steps=4):
    """Max abs logit error, quantized vs fp engine, over one prefill
    plus ``steps`` decode steps fed the same fp-argmax token stream."""
    cfg, _, _ = _setup(family)
    rng = np.random.default_rng(9)
    toks = rng.integers(0, cfg.vocab - 2, PROMPT_LEN).astype(np.int32)
    feng = _engine(family, None, None, max_slots=1)
    qeng = _engine(family, wd, kd, max_slots=1)
    batch = {"tokens": jnp.asarray(toks[:-1][None])}
    if cfg.family == "vlm":
        batch["vision"] = jnp.asarray(
            _vision(cfg, rng)["vision"][None])
    lf, cf = feng._prefill((feng.params, batch))
    lq, cq = qeng._prefill((qeng.params, batch))
    err = float(jnp.max(jnp.abs(lf[..., :cfg.vocab]
                                - lq[..., :cfg.vocab])))
    pos = PROMPT_LEN - 1 + (cfg.n_vision_tokens
                            if cfg.family == "vlm" else 0)
    cur = int(toks[-1])
    for _ in range(steps):
        curs = jnp.asarray([[cur]], jnp.int32)
        lens = jnp.asarray([pos], jnp.int32)
        lf, cf = feng._decode.consume((feng.params, cf, curs, lens))
        lq, cq = qeng._decode.consume((qeng.params, cq, curs, lens))
        err = max(err, float(jnp.max(jnp.abs(
            lf[:, :cfg.vocab] - lq[:, :cfg.vocab]))))
        cur = int(jnp.argmax(lf[0, :cfg.vocab]))
        pos += 1
    return err


# ---------------------------------------------------------------------------
# accuracy: quantized vs fp, tolerance-gated per family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wd", ("int8", "int4"))
def test_dense_logit_tolerance(wd):
    err = _logit_err("dense", wd, "int8")
    assert 0 < err <= TOLERANCE["dense"][wd], (wd, err)


@pytest.mark.slow
@pytest.mark.parametrize("family", ("moe", "vlm"))
@pytest.mark.parametrize("wd", ("int8", "int4"))
def test_family_logit_tolerance(family, wd):
    err = _logit_err(family, wd, "int8")
    assert 0 < err <= TOLERANCE[family][wd], (family, wd, err)


# ---------------------------------------------------------------------------
# self-identity: the bit-exact contracts quantization must keep
# ---------------------------------------------------------------------------

def test_quantized_preempt_restore_identity():
    """The acceptance gate: int8/int8 decode is bit-identical to
    itself across admit/preempt/restore, with exactly one decode
    program throughout."""
    base, e0 = _serve("dense", "int8", "int8")
    again, e1 = _serve("dense", "int8", "int8", evict=True)
    assert base == again
    assert jit_cache_size(e0._decode) == 1
    assert jit_cache_size(e1._decode) == 1
    assert e1.results[0].preemptions + sum(
        e1.results[u].preemptions for u in range(4)) >= 1
    # quantization shrank the resident footprint (weights AND KV)
    fp, ef = _serve("dense", None, None)
    assert ef.param_bytes / e0.param_bytes >= 1.5
    assert ef.kv_bytes / e0.kv_bytes >= 1.5


def test_paged_quantized_matches_contiguous():
    """Paging stays a LAYOUT change under quantization: the paged
    int8/int8 engine decodes the contiguous engine's exact tokens
    (block-table kernel dequant included) from one compiled program."""
    contig, _ = _serve("dense", "int8", "int8")
    paged, eng = _serve("dense", "int8", "int8", evict=True,
                        kv_block=8, kv_pool_blocks=2 * 4 + 1)
    assert paged == contig
    assert jit_cache_size(eng._decode) == 1


@pytest.mark.parametrize("wd,kd", (("int8", None), (None, "int8"),
                                   ("int4", "int8")))
def test_quantized_axes_compose_independently(wd, kd):
    """Each quantization axis works alone and combined: weight-only,
    KV-only, and int4+int8 engines all keep the self-identity and
    compile-once contracts."""
    base, e0 = _serve("dense", wd, kd)
    again, e1 = _serve("dense", wd, kd, evict=True)
    assert base == again
    assert jit_cache_size(e0._decode) == 1
    assert jit_cache_size(e1._decode) == 1


# ---------------------------------------------------------------------------
# typed refusals
# ---------------------------------------------------------------------------

def test_unsupported_quantization_raises_typed_errors():
    cfg, m, params = _setup("audio")
    with pytest.raises(UnsupportedFamilyError, match="quantized"):
        ServingEngine(m, params, cache_len=CACHE_LEN,
                      weight_dtype="int8")
    scfg, sm, sparams = _setup("ssm")
    with pytest.raises(UnsupportedFamilyError, match="int8 KV"):
        ServingEngine(sm, sparams, cache_len=CACHE_LEN,
                      weight_dtype="int8", kv_dtype="int8")
    dcfg, dm, dparams = _setup("dense")
    with pytest.raises(ValueError, match="weight_dtype"):
        ServingEngine(dm, dparams, cache_len=CACHE_LEN,
                      weight_dtype="int2")
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(dm, dparams, cache_len=CACHE_LEN,
                      kv_dtype="int4")
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(dm, dparams, cache_len=CACHE_LEN,
                      weight_dtype="int8", prefill_chunk=8)
    with pytest.raises(ValueError, match="mesh"):
        ServingEngine(dm, dparams, cache_len=CACHE_LEN,
                      weight_dtype="int8", mesh=object())
