"""Smoke run of both main paths on the TPU: the paper's micro interpreter
and the LLM serving engine, through their normal entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh-sharded engine only

One chip runs these phases in order:

  device   the first device is a TPU (no CPU fallback);
  micro    the VWW model (MobileNet-v1 0.25x, 96x96) exported to int8,
           invoked through ``MicroInterpreter`` with the Pallas kernels
           and checked against the same blob on the reference kernels,
           plus one batched ``InterpreterPool`` invoke;
  serve    phi4-mini-3.8b at its published widths with random weights:
           a contiguous engine serves a few requests, its decode
           program must hold a Pallas kernel (``tpu_custom_call``), its
           first decode logits must agree with a reference-kernel
           engine's, and a paged engine must emit the same tokens;
           one more decode step of each engine must reuse its KV's
           device buffers (the donation took, nothing copied the KV).

``--chips 4`` runs the device phase and deepseek-moe-16b on a
``ServingEngine`` sharded over a 4-chip mesh, with its first decode
logits checked against the plain forward of the same weights and its
decode step's KV kept in place, as above.

Any failed phase makes the exit code 1 and suppresses the result line.
The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.apps import build_vww  # noqa: E402
from repro.apps.models import representative_dataset  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import (AllOpsResolver, InterpreterPool,  # noqa: E402
                        MicroInterpreter, MicroModel, export)
from repro.kernels import ops as kernel_ops  # noqa: E402  tag="pallas"
from repro.kernels.ref import decode_attention_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_serving_mesh  # noqa: E402
from repro.launch.serve import build_engine  # noqa: E402
from repro.serving import Request, ServingEngine  # noqa: E402

PALLAS = ("pallas", "reference")
REFERENCE = ("reference",)
# int8 outputs: the Pallas FC requantizes in f32, the reference in
# gemmlowp fixed point — at most 1 LSB apart (tests/test_kernels.py)
MICRO_LSB = 1
# The decode-attention kernel at the engine's widths on random bf16
# inputs, against the f32 reference attention: max |a - b| / max |b|.
# The kernel rounds its output to bf16 once (2^-9 relative) and may
# round probabilities to bf16 in the MXU; a wrong mask, position or
# head mapping moves the output by O(1)
KERNEL_RTOL = 1e-2
# bf16 first-decode logits, Pallas decode vs reference attention (and,
# on the mesh, engine decode vs full forward), measured the same way
# over the true vocab.  The two paths round attention differently, and
# a random-weight model amplifies that through its depth: on the CPU,
# phi4-mini's reduced widths in bf16 give 0.003 at 2 layers and 0.14
# at 32, and its published widths 0.205 on a TPU v5e.  So this bound
# only catches gross faults (a wrong token or position gives 0.8-1.7);
# KERNEL_RTOL is the sharp check
LOGIT_RTOL = 0.5

_COMPILES: list = []


def _on_duration(event: str, secs: float, **kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES.append((kw.get("fun_name", "?"), secs))


def _compiles_since(mark: int) -> dict:
    """Backend compiles (persistent-cache loads included) since
    ``mark``: their count, total seconds, and each one over a second."""
    new = _COMPILES[mark:]
    return {"n": len(new), "total_s": round(sum(s for _, s in new), 3),
            "over_1s": [(n, round(s, 3)) for n, s in new if s >= 1.0]}


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _buffers(tree) -> list:
    return [[sh.data.unsafe_buffer_pointer() for sh in leaf.addressable_shards]
            for leaf in jax.tree.leaves(tree)]


def _kv_in_place(eng: ServingEngine) -> bool:
    """One more decode step of ``eng``: true when the step consumed the
    engine's KV and the engine kept the very device buffers it had, so
    the donation took and nothing (a changed sharding, the engine's
    re-pin) copied the KV."""
    kv = eng.kv_pool if eng.paged else eng.cache
    before = _buffers(kv)
    eng._dispatch_decode()
    after = eng.kv_pool if eng.paged else eng.cache
    return (all(leaf.is_deleted() for leaf in jax.tree.leaves(kv))
            and _buffers(after) == before)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_phase(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    _say("device", **info)
    if d.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX sees {d.platform!r} devices")
    if len(devs) < chips:
        raise RuntimeError(f"{chips} chips asked, {len(devs)} visible")
    info["count"] = chips
    return info


def _invoke(model: MicroModel, tags, x: np.ndarray) -> np.ndarray:
    resolver = AllOpsResolver(tags)
    it = MicroInterpreter(model, resolver,
                          MicroInterpreter.required_arena_size(model,
                                                               resolver))
    it.set_input(0, x)
    it.invoke()
    return it.output(0)


def micro_phase(batch: int = 4) -> None:
    """int8 VWW through the interpreter: Pallas vs reference kernels,
    then one batched ``InterpreterPool`` invoke."""
    mark = len(_COMPILES)
    gb = build_vww()
    blob = export(build_vww(), representative_dataset=
                  representative_dataset(gb, n=8), quantize_int8=True)
    model = MicroModel(blob)
    # the blob dequantizes its int8 output; compare in that tensor's LSBs
    out = model.outputs[0]
    last = next(op for op in model.operators if out in op.outputs)
    lsb_scale = model.tensors[last.inputs[0]].quant.scale
    rng = np.random.default_rng(0)
    shape = model.tensors[model.inputs[0]].shape
    xs = [rng.normal(0, 1, shape).astype(np.float32)
          for _ in range(batch)]
    t0 = time.perf_counter()
    got = _invoke(model, PALLAS, xs[0])
    t_pallas = time.perf_counter() - t0
    want = [_invoke(model, REFERENCE, x) for x in xs]
    lsb = float(np.abs(got - want[0]).max() / lsb_scale)
    pool = InterpreterPool(model, AllOpsResolver(PALLAS), batch)
    for lane, x in enumerate(xs):
        pool.set_input(lane, 0, x)
    t0 = time.perf_counter()
    pool.invoke()
    outs = pool.outputs(0)
    t_pool = time.perf_counter() - t0
    pool_lsb = float(max(np.abs(outs[i] - want[i]).max()
                         for i in range(batch)) / lsb_scale)
    _say("micro", model="vww-mobilenet-v1-0.25x-96-int8",
         blob_bytes=len(blob), lsb_scale=lsb_scale,
         max_lsb=lsb, pool_batch=batch, pool_max_lsb=pool_lsb,
         bound_lsb=MICRO_LSB, first_invoke_s=round(t_pallas, 3),
         first_pool_invoke_s=round(t_pool, 3),
         compiles=_compiles_since(mark), peak_bytes=_peak_bytes())
    if lsb > MICRO_LSB or pool_lsb > MICRO_LSB:
        raise AssertionError(f"Pallas vs reference: {lsb} / {pool_lsb} "
                             f"LSB > {MICRO_LSB}")


def _kernel_err(cfg, batch: int, cache_len: int, mesh=None) -> float:
    """The decode-attention kernel (per head shard on ``mesh``) at
    ``cfg``'s head widths against the f32 reference attention, on
    random bf16 inputs with lengths from 1 to ``cache_len``."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    dt = cfg.jnp_dtype()
    q = jax.random.normal(kq, (batch, cfg.n_heads, cfg.dh), dt)
    cache = (batch, cfg.n_kv_heads, cache_len, cfg.dh)
    k = jax.random.normal(kk, cache, dt)
    v = jax.random.normal(kv, cache, dt)
    lengths = jnp.asarray(np.linspace(1, cache_len, batch), jnp.int32)
    attn = kernel_ops.per_head_shard(kernel_ops.decode_attention, mesh,
                                     cfg.n_kv_heads)
    got = jax.jit(attn)(q, k, v, lengths)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(decode_attention_ref)(q.astype(jnp.float32),
                                             k, v, lengths)
    return _rel_err(got, want)


def _requests(cfg, n: int, lens, max_new: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, tokens=rng.integers(
                0, cfg.vocab - 2, int(lens[i])).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def _record_first_decode(eng: ServingEngine) -> dict:
    """Keep the inputs, logits and slot requests of ``eng``'s first
    decode step; ``box["program"]`` is the jitted decode step.  The
    first step is a plain call, which donates a copy of the cache and
    so leaves the kept inputs valid for a replay; later steps go back
    to the engine's own dispatch, which consumes the cache in place."""
    box = {"program": eng._decode}

    def decode(inputs):
        out = box["program"](inputs)
        box.update(inputs=inputs, logits=out[0],
                   reqs=[eng.slot_meta[s] if eng.active[s] else None
                         for s in range(eng.max_slots)])
        eng._decode = box["program"]
        return out

    eng._decode = decode
    return box


def _serve(eng: ServingEngine, reqs) -> tuple:
    for r in reqs:
        eng.submit(dataclasses.replace(r))
    t0 = time.perf_counter()
    res = eng.run()
    wall = time.perf_counter() - t0
    return {u: list(r.output) for u, r in res.items()}, wall


def serve_phase(cfg, *, n_requests: int = 8, slots: int = 4,
                cache_len: int = 512, max_new: int = 16,
                prompt_lens=(33, 128), kv_block: int = 128) -> None:
    """Contiguous engine with the Pallas decode kernel, checked against
    a reference-kernel engine and a paged engine on the same weights."""
    mark = len(_COMPILES)
    t0 = time.perf_counter()
    eng = build_engine(cfg, max_slots=slots, cache_len=cache_len,
                       tags=PALLAS)
    jax.block_until_ready(eng.params)
    t_init = time.perf_counter() - t0
    rng = np.random.default_rng(1)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, n_requests)
    reqs = _requests(cfg, n_requests, lens, max_new)
    box = _record_first_decode(eng)
    tokens, wall = _serve(eng, reqs)
    n_tok = sum(len(t) for t in tokens.values())

    t0 = time.perf_counter()
    hlo = box["program"].lower(box["inputs"]).compile().as_text()
    t_hlo = time.perf_counter() - t0
    has_kernel = "tpu_custom_call" in hlo
    kernel_err = _kernel_err(cfg, slots, cache_len)
    ref = ServingEngine(eng.bundle, eng.params, max_slots=slots,
                        cache_len=cache_len, tags=REFERENCE)
    ref_logits, _ = ref._decode(box["inputs"])
    rows = [s for s, r in enumerate(box["reqs"]) if r is not None]
    err = _rel_err(box["logits"][rows, :cfg.vocab],
                   ref_logits[rows, :cfg.vocab])
    paged = ServingEngine(eng.bundle, eng.params, max_slots=slots,
                          cache_len=cache_len, kv_block=kv_block,
                          tags=PALLAS)
    paged_tokens, paged_wall = _serve(paged, reqs)
    same = paged_tokens == tokens
    in_place = [_kv_in_place(eng), _kv_in_place(paged)]
    _say("serve", arch=cfg.arch_id, layers=cfg.n_layers,
         d_model=cfg.d_model, vocab=cfg.vocab, dtype=cfg.dtype,
         param_bytes=eng.param_bytes, kv_bytes=eng.kv_bytes,
         requests=n_requests, slots=slots, cache_len=cache_len,
         prompt_lens=[int(x) for x in lens], init_s=round(t_init, 3),
         wall_s=round(wall, 3), tokens_generated=n_tok,
         decode_hlo_has_tpu_custom_call=has_kernel,
         hlo_recompile_s=round(t_hlo, 3),
         kernel_rel_err=kernel_err, kernel_rel_tol=KERNEL_RTOL,
         first_decode_rel_err=err, rel_tol=LOGIT_RTOL,
         paged_kv_block=kv_block, paged_wall_s=round(paged_wall, 3),
         paged_tokens_identical=same, kv_in_place=in_place,
         compiles=_compiles_since(mark), peak_bytes=_peak_bytes())
    if has_kernel == kernel_ops.interpret_mode():
        raise AssertionError(
            f"decode HLO tpu_custom_call={has_kernel} on "
            f"{jax.default_backend()}")
    if not n_tok == n_requests * max_new:
        raise AssertionError(f"{n_tok} tokens, wanted "
                             f"{n_requests * max_new}")
    if not kernel_err <= KERNEL_RTOL:
        raise AssertionError(f"decode kernel vs reference attention: "
                             f"{kernel_err} > {KERNEL_RTOL}")
    if not err <= LOGIT_RTOL:
        raise AssertionError(f"Pallas vs reference decode logits: "
                             f"{err} > {LOGIT_RTOL}")
    if not same:
        raise AssertionError("paged engine tokens differ from contiguous")
    if not all(in_place):
        raise AssertionError(f"decode step copied its KV (contiguous, "
                             f"paged in place: {in_place})")


def sharded_phase(cfg, chips: int, *, slots: int = 4,
                  cache_len: int = 256, prompt_len: int = 64,
                  max_new: int = 8) -> None:
    """A ``ServingEngine`` over a ``chips``-way model mesh, weights made
    straight into their shardings; first decode logits vs the plain
    forward of the same weights on the same mesh.  The engine prefills
    each prompt but its last token, which the first decode step
    consumes, so that step's logits are the forward's last-position
    logits over the whole prompt."""
    mark = len(_COMPILES)
    mesh = make_serving_mesh(chips)
    t0 = time.perf_counter()
    eng = build_engine(cfg, mesh=mesh, max_slots=slots,
                       cache_len=cache_len, tags=PALLAS)
    jax.block_until_ready(eng.params)
    t_init = time.perf_counter() - t0
    per_dev = {}
    for leaf in jax.tree.leaves(eng.params):
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = (per_dev.get(sh.device.id, 0)
                                     + sh.data.nbytes)
    reqs = _requests(cfg, slots, [prompt_len] * slots, max_new)
    box = _record_first_decode(eng)
    tokens, wall = _serve(eng, reqs)
    n_tok = sum(len(t) for t in tokens.values())
    in_place = _kv_in_place(eng)

    rows = [s for s, r in enumerate(box["reqs"]) if r is not None]
    seqs = np.stack([box["reqs"][s].tokens for s in rows])
    bundle = eng.bundle
    fwd = jax.jit(lambda p, t: bundle.prefill(p, {"tokens": t})[0])
    want = fwd(eng.params, jnp.asarray(seqs))
    err = _rel_err(box["logits"][rows, :cfg.vocab],
                   want[:, :cfg.vocab])
    kernel_err = _kernel_err(cfg, slots, cache_len, mesh)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in mesh.devices.flat]
    _say("sharded", arch=cfg.arch_id, chips=chips,
         param_bytes=eng.param_bytes,
         param_bytes_per_device=sorted(per_dev.values()),
         init_s=round(t_init, 3), wall_s=round(wall, 3),
         tokens_generated=n_tok, kernel_rel_err=kernel_err,
         kernel_rel_tol=KERNEL_RTOL,
         first_decode_vs_forward_rel_err=err,
         rel_tol=LOGIT_RTOL, kv_in_place=in_place,
         compiles=_compiles_since(mark), peak_bytes_per_device=peaks)
    if max(per_dev.values()) >= eng.param_bytes:
        raise AssertionError("one device holds the whole model")
    if not n_tok == slots * max_new:
        raise AssertionError(f"{n_tok} tokens, wanted {slots * max_new}")
    if not kernel_err <= KERNEL_RTOL:
        raise AssertionError(f"per-shard decode kernel vs reference "
                             f"attention: {kernel_err} > {KERNEL_RTOL}")
    if not err <= LOGIT_RTOL:
        raise AssertionError(f"sharded decode vs forward logits: "
                             f"{err} > {LOGIT_RTOL}")
    if not in_place:
        raise AssertionError("sharded decode step copied its KV")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    info = device_phase(args.chips)
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    if args.chips == 1:
        phases = [("micro", micro_phase),
                  ("serve", lambda: serve_phase(
                      get_config("phi4-mini-3.8b")))]
    else:
        phases = [("sharded", lambda: sharded_phase(
            get_config("deepseek-moe-16b"), args.chips))]
    failed = []
    for name, fn in phases:
        try:
            fn()
        except Exception:
            traceback.print_exc()
            _say(name, failed=True)
            failed.append(name)
    _say("done", wall_s=round(time.perf_counter() - t0, 3),
         failed=failed)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
