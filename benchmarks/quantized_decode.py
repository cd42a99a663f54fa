"""Quantized pod decode: int8/int4 weights + int8 KV vs the fp engine
at matched batch (docs/QUANTIZATION.md).

Decode is memory-bound, so the quantization win shows up on two axes:

  * **HBM footprint** — the resident bytes a serving engine pins
    (quantized weight tree + KV arena), which is what bounds how many
    replicas/tenants fit a device;
  * **decoded-token fidelity** — the quantized engine is NOT
    bit-identical to fp (that is the documented contract: a per-family
    logit tolerance, gated in tests/test_quant_serving.py), but it
    must be bit-identical to ITSELF across admit/preempt/restore —
    the ``tokens_match`` column replays each quantized run with a
    forced mid-run eviction and asserts token identity.

Rows: one per (family, weight_dtype, kv_dtype) cell — ``fp32`` rows
are the unquantized baseline at the same slot count, so footprint
reductions read straight off the table.  ``tokens_per_s`` prices one
warm fused decode dispatch at ``SLOTS`` concurrent slots (CPU
interpret-mode Pallas: the number is a layout-overhead proxy, not
hardware throughput — same caveat as kernel_speedup).  Emits
``BENCH_quantized_decode.json`` unless ``tiny``.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from .common import block, print_table, save_result, time_call

SEED = 11
ARCHS = {"dense": "qwen3-32b", "moe": "deepseek-moe-16b",
         "vlm": "paligemma-3b"}
# (weight_dtype, kv_dtype) cells; "fp32" = that axis unquantized
MODES = (("fp32", "fp32"), ("int8", "int8"), ("int4", "int8"))
CACHE_LEN = 32
SLOTS = 4
PROMPT_LEN = 6
N_NEW = 6
ERR_STEPS = 4
# documented max-abs logit tolerance vs the fp engine, per family ×
# weight dtype (the accuracy gate in tests/test_quant_serving.py uses
# the same numbers; docs/QUANTIZATION.md explains the spread: moe is
# loosest because weight rounding can flip discrete expert routing,
# vlm amplifies embedding error through its sqrt(d_model) scale)
TOLERANCE = {
    "dense": {"int8": 0.5, "int4": 2.0},
    "moe": {"int8": 2.5, "int4": 4.0},
    "vlm": {"int8": 1.5, "int4": 4.0},
}


def _setup(family: str):
    import jax

    from repro.configs import get_config
    from repro.models import get_model

    cfg = get_config(ARCHS[family], reduced=True)
    bundle = get_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    return cfg, bundle, params


def _engine(bundle, params, wd: str, kd: str, *, slots: int):
    from repro.serving import ServingEngine

    return ServingEngine(
        bundle, params, max_slots=slots, cache_len=CACHE_LEN,
        prefill_buckets=False,
        weight_dtype=None if wd == "fp32" else wd,
        kv_dtype=None if kd == "fp32" else kd)


def _prefill_batch(cfg, rng, toks):
    import jax.numpy as jnp

    batch = {"tokens": jnp.asarray(np.asarray(toks)[None])}
    if cfg.family == "vlm":
        batch["vision"] = jnp.asarray(rng.normal(
            0, 1, (1, cfg.n_vision_tokens, cfg.d_vision)
        ).astype(np.float32))
    return batch


def _logit_err(cfg, bundle, params, wd: str, kd: str) -> float:
    """Max abs logit error of the quantized engine vs the fp engine
    over one prefill plus ``ERR_STEPS`` decode steps, both fed the
    SAME (fp-argmax) token stream so the states stay comparable."""
    import jax.numpy as jnp

    rng = np.random.default_rng(SEED)
    toks = rng.integers(0, cfg.vocab - 2, PROMPT_LEN).astype(np.int32)
    feng = _engine(bundle, params, "fp32", "fp32", slots=1)
    qeng = _engine(bundle, params, wd, kd, slots=1)
    batch = _prefill_batch(cfg, rng, toks[:-1])
    lf, cf = feng._prefill((feng.params, batch))
    lq, cq = qeng._prefill((qeng.params, batch))
    err = float(jnp.max(jnp.abs(lf[..., :cfg.vocab]
                                - lq[..., :cfg.vocab])))
    vis = cfg.n_vision_tokens if cfg.family == "vlm" else 0
    pos = PROMPT_LEN - 1 + vis
    cur = int(toks[-1])
    for _ in range(ERR_STEPS):
        curs = jnp.asarray([[cur]], jnp.int32)
        lens = jnp.asarray([pos], jnp.int32)
        lf, cf = feng._decode.consume((feng.params, cf, curs, lens))
        lq, cq = qeng._decode.consume((qeng.params, cq, curs, lens))
        err = max(err, float(jnp.max(jnp.abs(
            lf[:, :cfg.vocab] - lq[:, :cfg.vocab]))))
        cur = int(jnp.argmax(lf[0, :cfg.vocab]))
        pos += 1
    return err


def _serve(cfg, bundle, params, wd: str, kd: str,
           evict_at: Optional[int]) -> List[List[int]]:
    """Serve 4 requests through a 2-slot quantized engine, optionally
    forcing an eviction mid-run — the preempt/restore replay leg of
    ``tokens_match``."""
    from repro.serving import Request

    eng = _engine(bundle, params, wd, kd, slots=2)
    rng = np.random.default_rng(SEED + 1)
    extras = None
    if cfg.family == "vlm":
        extras = {"vision": rng.normal(
            0, 1, (cfg.n_vision_tokens, cfg.d_vision)
        ).astype(np.float32)}
    for uid in range(4):
        toks = rng.integers(0, cfg.vocab - 2,
                            PROMPT_LEN).astype(np.int32)
        eng.submit(Request(uid=uid, tokens=toks, max_new_tokens=N_NEW,
                           extras=extras))
    steps, more = 0, True
    while more:
        more = eng.step()
        steps += 1
        if evict_at is not None and steps == evict_at:
            victims = [s for s in range(eng.max_slots)
                       if eng.active[s]]
            if victims:
                eng._evict(victims[0])
        if steps > 400:
            raise RuntimeError("serving loop did not converge")
    return [list(eng.results[u].output) for u in range(4)]


def _decode_rate(eng) -> float:
    """Warm tokens/s of one fused decode dispatch at full occupancy."""
    import jax.numpy as jnp

    b = eng.max_slots
    # the serving loop's dispatch: the engine's KV donated and kept
    eng.cur_tokens = jnp.zeros((b, 1), jnp.int32)
    eng.lengths = jnp.full((b,), CACHE_LEN // 2, jnp.int32)
    t = time_call(lambda: block(eng._dispatch_decode()), warmup=2, iters=5)
    return b / t


def run(tiny: bool = False) -> List[Dict]:
    families = ("dense",) if tiny else tuple(ARCHS)
    modes = MODES[:2] if tiny else MODES
    rows: List[Dict] = []
    for family in families:
        cfg, bundle, params = _setup(family)
        fp_hbm: Optional[int] = None
        for wd, kd in modes:
            eng = _engine(bundle, params, wd, kd, slots=SLOTS)
            hbm = int(eng.param_bytes + eng.kv_bytes)
            if wd == "fp32":
                fp_hbm = hbm
            rate = _decode_rate(eng)
            err = (0.0 if wd == "fp32"
                   else _logit_err(cfg, bundle, params, wd, kd))
            tol = 0.0 if wd == "fp32" else TOLERANCE[family][wd]
            assert err <= tol, (family, wd, kd, err, tol)
            straight = _serve(cfg, bundle, params, wd, kd, None)
            evicted = _serve(cfg, bundle, params, wd, kd, 3)
            match = straight == evicted
            assert match, (family, wd, kd,
                           "quantized decode must be bit-identical "
                           "to itself across preempt/restore")
            rows.append({
                "family": family, "weight_dtype": wd, "kv_dtype": kd,
                "tokens_per_s": round(rate, 1), "hbm_bytes": hbm,
                "hbm_reduction": round(fp_hbm / hbm, 2),
                "max_abs_logit_err": round(err, 4),
                "tokens_match": bool(match),
            })
    # the headline claim: int8 weights + int8 KV must shrink the
    # resident footprint by at least 1.5x vs fp at the same batch
    for family in families:
        fam = [r for r in rows if r["family"] == family]
        i8 = next(r for r in fam if r["weight_dtype"] == "int8")
        assert i8["hbm_reduction"] >= 1.5, (family, i8)
    print_table("Quantized pod decode vs fp at matched batch "
                f"({SLOTS} slots, cache_len {CACHE_LEN})", rows)
    if not tiny:
        save_result("BENCH_quantized_decode", rows, seed=SEED)
    return rows


if __name__ == "__main__":
    run()
