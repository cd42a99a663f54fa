"""Cross-family conformance matrix (family-parity acceptance).

Every seed config family is driven through every serving fast path it
supports — exact-length, bucketed, chunked, checkpointed (a forced
mid-run preempt/restore cycle), paged where the cache layout allows,
sharded (the same forced preempt/restore cycle on a 2-device
``("data", "model")`` mesh, params and KV partitioned over the
``model`` axis), and streaming (overlapped decode with per-token
``StreamEvent`` callbacks, plus the same forced preempt/restore
cycle) — and each run's decoded tokens must be IDENTICAL to
that family's exact-length baseline:

  * dense/vlm: length-masked decode hides bucket/chunk padding;
  * moe: masked dropless dispatch (``lm.moe_dispatch``) makes bucket
    padding invisible to expert routing;
  * ssm/hybrid: the recurrent-state chunk op
    (``SERVING_PREFILL_CHUNK_STATE``) carries (conv, SSD) state across
    chunk boundaries with the padded tail an exact state no-op;
  * every family: checkpoint/restore replays bit-identically because
    the decode step is a pure function of the restored slot state
    (``extract_slot_state`` / ``insert_slot_state``).

Alongside token identity, every run asserts the compile-once
invariant: ONE decode program, ONE chunk program, one prefill program
per bucket (not per length) — and a preempt/restore cycle traces
NOTHING new (``jit_cache_size`` never grows across admit → evict →
restore).

Combinations a family does NOT support must refuse with the typed
``UnsupportedFamilyError`` naming family, feature, and the supported
set — asserted for every remaining guard.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.executor import BucketTable, jit_cache_size
from repro.launch.mesh import make_serving_mesh
from repro.models import get_model
from repro.serving import Request, ServingEngine, UnsupportedFamilyError

ARCHS = {
    "dense": "qwen3-32b",
    "moe": "deepseek-moe-16b",
    "ssm": "mamba2-780m",
    "hybrid": "zamba2-1.2b",
    "vlm": "paligemma-3b",
    "audio": "whisper-large-v3",
}

# the conformance matrix: which fast paths each family supports.
# "exact" is the baseline every other mode is compared against;
# "checkpointed" is exact + a forced mid-run evict/restore.
MATRIX = {
    "dense": ("exact", "bucketed", "chunked", "checkpointed", "paged",
              "sharded", "streaming"),
    "moe": ("exact", "bucketed", "checkpointed", "paged", "sharded",
            "streaming"),
    "ssm": ("exact", "chunked", "checkpointed", "sharded", "streaming"),
    "hybrid": ("exact", "chunked", "checkpointed", "sharded",
               "streaming"),
    "vlm": ("exact", "bucketed", "chunked", "checkpointed", "paged",
            "sharded", "streaming"),
    "audio": ("exact", "checkpointed"),
}

# modes that force a mid-run evict/restore cycle while running;
# streaming joins so the exactly-once callback contract is proven
# ACROSS preemption, not just on the happy path
_EVICT_MODES = ("checkpointed", "sharded", "streaming")

# the sharded column needs a real 2-device mesh; tier-1 runs on one
# CPU device, so these cells only light up under
# XLA_FLAGS=--xla_force_host_platform_device_count=2 (CI slow tier)
_SHARDED_SKIP = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="sharded matrix needs >=2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")

PROMPT_LENS = (21, 13, 30, 9)
N_NEW = 6
CACHE_LEN = 64
CHUNK = 8
KV_BLOCK = 8

_SETUP = {}


def _setup(family):
    """(cfg, bundle, params, requests) for a family — cached so the
    matrix re-uses one weight init per family across modes."""
    if family not in _SETUP:
        cfg = get_config(ARCHS[family], reduced=True)
        m = get_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(5)
        reqs = []
        for uid, n in enumerate(PROMPT_LENS):
            toks = rng.integers(0, cfg.vocab - 2, n).astype(np.int32)
            extras = None
            if family == "vlm":
                extras = {"vision": rng.normal(
                    0, 1, (cfg.n_vision_tokens, cfg.d_vision)
                ).astype(np.float32)}
            elif family == "audio":
                extras = {"frames": rng.normal(
                    0, 1, (cfg.n_audio_ctx, cfg.d_model)
                ).astype(np.float32)}
            reqs.append((uid, toks, extras))
        _SETUP[family] = (cfg, m, params, reqs)
    return _SETUP[family]


def _cache_len(cfg):
    # a vlm's vision prefix occupies cache rows in front of the prompt
    return CACHE_LEN + (cfg.n_vision_tokens if cfg.family == "vlm"
                        else 0)


_MODE_KW = {
    "exact": {"prefill_buckets": False},
    "checkpointed": {"prefill_buckets": False},
    "bucketed": {"prefill_buckets": True},
    "chunked": {"prefill_buckets": False, "prefill_chunk": CHUNK},
    "paged": {"prefill_buckets": False, "kv_block": KV_BLOCK},
    # the mesh itself is built lazily in _run (needs >=2 devices)
    "sharded": {"prefill_buckets": False},
    # overlapped decode + per-token StreamEvent callbacks (the
    # on_token sink is wired per-run in _run)
    "streaming": {"prefill_buckets": False, "overlap": True},
}


def _run(family, mode):
    """Run the family's request set through one matrix mode; returns
    ({uid: tokens}, engine)."""
    cfg, m, params, reqs = _setup(family)
    kw = dict(_MODE_KW[mode])
    if mode == "sharded":
        kw["mesh"] = make_serving_mesh(2)
    events = []
    if mode == "streaming":
        kw["on_token"] = events.append
    eng = ServingEngine(m, params, max_slots=2,
                        cache_len=_cache_len(cfg), **kw)
    for uid, toks, extras in reqs:
        eng.submit(Request(uid=uid, tokens=toks, max_new_tokens=N_NEW,
                           extras=extras))
    evicted = False
    traced_at_evict = None
    steps = 0
    while eng.step():
        steps += 1
        assert steps < 500, f"{family}/{mode} did not converge"
        if mode in _EVICT_MODES and not evicted and steps >= 3:
            # forced preemption: checkpoint whichever slot is busy,
            # re-queue it, and record the trace counts the later
            # restore must not grow.  The overlapped engine must be
            # quiesced first — a pending readback may retire the slot
            # we are about to pick (the drain-before-surgery contract
            # every checkpoint path follows internally).
            if mode == "streaming":
                eng.drain()
            victim = next((s for s in range(eng.max_slots)
                           if eng.active[s] or s in eng._chunking),
                          None)
            if victim is not None:
                eng._evict(victim)
                evicted = True
                traced_at_evict = (eng.prefill_compiles(),
                                   jit_cache_size(eng._decode))
    outs = {uid: eng.results[uid].output for uid, _, _ in reqs}
    # ---- compile-once invariants, every mode ------------------------
    assert jit_cache_size(eng._decode) == 1, (family, mode)
    if mode == "chunked":
        assert eng.chunk_compiles() == 1, (family, mode)
        # the only one-shot prefill shape is the chunk-ineligible short
        # prompt (and dense/vlm's fixed-shape first chunk shares it);
        # recurrent families push EVERY chunk through the chunk op
        assert eng.prefill_compiles() <= 1, (family, mode)
    if mode == "bucketed":
        hit = {eng.bucket_table.fit(n - 1) for n in PROMPT_LENS}
        assert eng.prefill_compiles() == len(hit), (family, mode)
        assert eng.prefill_compiles() < len(set(PROMPT_LENS))
    if mode in _EVICT_MODES:
        assert evicted, f"{family}: nothing was running to evict"
        assert eng.results[0].preemptions \
            + sum(eng.results[u].preemptions for u, _, _ in reqs) >= 1
        # restore traced nothing: counts frozen at eviction time may
        # grow only by NOT-YET-ADMITTED prompts' prefills, never by
        # the restore itself — decode stays at exactly one program.
        # On a mesh this additionally proves the pinning discipline:
        # evict pulls KV to host, restore re-commits it to the cache
        # sharding, and neither placement round-trip retraces.
        assert jit_cache_size(eng._decode) == traced_at_evict[1] == 1
    if mode == "streaming":
        # callback ordering contract (docs/STREAMING.md): per request,
        # indices run 0..n-1 in emission order, the streamed tokens ARE
        # the accumulated output (each exactly once — across the forced
        # evict/restore above), exactly the last event is final, and
        # timestamps never run backwards
        per = {}
        for ev in events:
            per.setdefault(ev.uid, []).append(ev)
        assert sorted(per) == sorted(outs), (family, sorted(per))
        for uid, evs in per.items():
            assert [e.index for e in evs] == list(range(len(evs))), uid
            assert [e.token for e in evs] == outs[uid], (family, uid)
            assert [e.final for e in evs] == \
                [False] * (len(evs) - 1) + [True], (family, uid)
            ts = [e.t_us for e in evs]
            assert ts == sorted(ts), (family, uid)
    return outs, eng


@pytest.mark.slow
@pytest.mark.parametrize("family,mode", [
    pytest.param(fam, mode,
                 marks=(_SHARDED_SKIP,) if mode == "sharded" else ())
    for fam, modes in MATRIX.items() for mode in modes
    if mode != "exact"])
def test_family_mode_matches_exact_baseline(family, mode):
    """THE matrix: every supported (family, fast-path) cell decodes the
    exact same tokens as that family's exact-length baseline."""
    base, _ = _run(family, "exact")
    got, _ = _run(family, mode)
    assert got == base, (family, mode, got, base)


@pytest.mark.slow
@pytest.mark.parametrize("family", sorted(MATRIX))
def test_family_exact_baseline_is_nontrivial(family):
    """The baseline itself decodes full budgets (no silent empty
    outputs making the matrix vacuous) with one decode program."""
    base, eng = _run(family, "exact")
    for uid, toks in base.items():
        assert len(toks) >= 1, (family, uid)
    assert jit_cache_size(eng._decode) == 1


def test_checkpoint_state_roundtrip_recurrent():
    """The state-extraction hook carries SSM/hybrid recurrent state
    bit-exactly: extract a decoding slot's state, zero the slot, insert
    the copy back into a DIFFERENT slot, and the pytrees match leaf for
    leaf (conv window, SSD state, and hybrid's shared-attn KV)."""
    for family in ("ssm", "hybrid"):
        cfg, m, params, reqs = _setup(family)
        eng = ServingEngine(m, params, max_slots=2,
                            cache_len=_cache_len(cfg),
                            prefill_buckets=False)
        uid, toks, extras = reqs[0]
        eng.submit(Request(uid=uid, tokens=toks, max_new_tokens=N_NEW,
                           extras=extras))
        for _ in range(3):
            eng.step()
        state = eng.extract_slot_state(0)
        eng.insert_slot_state(1, jax.tree.map(np.asarray, state))
        back = eng.extract_slot_state(1)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
            assert a.dtype == b.dtype
            assert np.array_equal(a, b)


def test_unsupported_combinations_raise_typed_errors():
    """Every remaining family×feature hole refuses with the typed
    UnsupportedFamilyError naming family, feature, and supported set —
    no bare ValueError guards left on the engine fast paths."""
    cases = [
        ("ssm", {"prefill_buckets": BucketTable()}, "bucketed prefill"),
        ("hybrid", {"prefill_buckets": BucketTable()},
         "bucketed prefill"),
        ("moe", {"prefill_chunk": CHUNK}, "chunked prefill"),
        ("audio", {"prefill_chunk": CHUNK}, "chunked prefill"),
        ("ssm", {"kv_block": KV_BLOCK}, "paged KV"),
        ("hybrid", {"kv_block": KV_BLOCK}, "paged KV"),
        ("audio", {"kv_block": KV_BLOCK}, "paged KV"),
        # a model=1 mesh exists on any device count, and the family
        # gate fires before any sharding is computed — so the audio
        # refusal is asserted even in the single-device tier
        ("audio", {"mesh": make_serving_mesh(1)},
         "mesh-sharded serving"),
        # audio's encoder-decoder path is not qualified for deferred
        # readback (see STREAMING_FAMILIES), so overlap refuses typed
        ("audio", {"overlap": True}, "overlapped (async) decode"),
    ]
    for family, kw, feature in cases:
        cfg, m, params, _ = _setup(family)
        with pytest.raises(UnsupportedFamilyError) as ei:
            ServingEngine(m, params, max_slots=1,
                          cache_len=_cache_len(cfg), **kw)
        msg = str(ei.value)
        assert cfg.family in msg and feature in msg, (family, kw, msg)
        assert ei.value.supported, (family, kw)
        # the typed error still satisfies old except ValueError callers
        assert isinstance(ei.value, ValueError)


def test_moe_chunked_also_refused_when_paged():
    """MoE's chunk refusal holds on the paged engine too (the paged
    chunk op's prepare() re-checks the gate)."""
    cfg, m, params, _ = _setup("moe")
    with pytest.raises(UnsupportedFamilyError):
        ServingEngine(m, params, max_slots=1, cache_len=_cache_len(cfg),
                      prefill_chunk=CHUNK, kv_block=KV_BLOCK)


# ---------------------------------------------------------------------------
# quantized conformance cells (PR 10, docs/QUANTIZATION.md)
# ---------------------------------------------------------------------------

# which quantization layout each family serves: lm-path families take
# the full int8 weight + int8 KV pair; recurrent families are
# weight-only (their conv/SSD state is not a (KH, C, dh) KV ring).
# audio is outside WEIGHT_QUANT_FAMILIES — its refusal is asserted in
# tests/test_quant_serving.py.
_QUANT_KW = {
    "dense": {"weight_dtype": "int8", "kv_dtype": "int8"},
    "moe": {"weight_dtype": "int8", "kv_dtype": "int8"},
    "vlm": {"weight_dtype": "int8", "kv_dtype": "int8"},
    "ssm": {"weight_dtype": "int8"},
    "hybrid": {"weight_dtype": "int8"},
}


def _run_quantized(family, *, evict):
    """The family's request set through its quantized engine; returns
    ({uid: tokens}).  Deliberately NOT compared against the exact fp
    baseline — quantized decode is tolerance-gated against fp
    (tests/test_quant_serving.py), and bit-gated only against itself."""
    cfg, m, params, reqs = _setup(family)
    eng = ServingEngine(m, params, max_slots=2,
                        cache_len=_cache_len(cfg),
                        prefill_buckets=False, **_QUANT_KW[family])
    for uid, toks, extras in reqs:
        eng.submit(Request(uid=uid, tokens=toks, max_new_tokens=N_NEW,
                           extras=extras))
    evicted = False
    steps = 0
    while eng.step():
        steps += 1
        assert steps < 500, f"{family}/quantized did not converge"
        if evict and not evicted and steps >= 3:
            victim = next((s for s in range(eng.max_slots)
                           if eng.active[s]), None)
            if victim is not None:
                eng._evict(victim)
                evicted = True
    assert jit_cache_size(eng._decode) == 1, (family, "quantized")
    assert not evict or evicted, (family, "nothing running to evict")
    return {uid: eng.results[uid].output for uid, _, _ in reqs}


@pytest.mark.slow
@pytest.mark.parametrize("family", sorted(_QUANT_KW))
def test_family_quantized_preempt_restore_identity(family):
    """The quantized column of the conformance matrix: every family
    the SERVING_*_Q ops serve decodes bit-identical tokens with and
    without a forced mid-run evict/restore, from one compiled decode
    program — the compile-once contract survives quantization for the
    whole family matrix."""
    base = _run_quantized(family, evict=False)
    got = _run_quantized(family, evict=True)
    assert got == base, (family, got, base)
    # and the cells are non-trivial: every request decoded its budget
    assert all(len(t) == N_NEW for t in base.values()), (family, base)
