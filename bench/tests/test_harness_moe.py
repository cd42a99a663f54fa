"""The moe family against the program on the CPU, at a size of its own:
the engine's bucketed prefill and cached decode, on the family's seeded
weights, give the logits the family's plain routed reference gives."""

import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, peaks  # noqa: E402

CELL = "deepseek-moe-16b.chat.4chip"
# dense layer 0 and two MoE layers; 8 experts, top 3, one shared, the
# top-3 weights not renormalised, as the configuration routes
SMALL_MOE = dict(num_hidden_layers=3, hidden_size=256,
                 num_attention_heads=4, num_key_value_heads=4,
                 intermediate_size=512, moe_intermediate_size=128,
                 n_routed_experts=8, num_experts_per_tok=3,
                 n_shared_experts=1, vocab_size=512, vocab_padded=2048,
                 torch_dtype="float32")
SEED = 2**31 + 15
# both sides compute in float32 (the program on the CPU, the reference
# at highest precision), so they differ by summation order alone: about
# 1e-5 of logits of unit scale.  Renormalising the top-3 weights (about
# 0.4 of the gate's mass) moves the logits by far more than this.
LOGIT_TOL = 1e-3


def small_cell() -> harness.Cell:
    cell = harness.load_cell(CELL)
    cell.conf.update(SMALL_MOE)
    cell.conf["engine"].update(max_slots=4, cache_len=128, overlap=False)
    cell.chips = 1
    return cell


def _serve(cell, prompts, max_new):
    """Serve ``prompts`` through the engine; per request, the decode
    logits it produced at each position and the tokens it served."""
    from repro.serving import Request
    engine = harness.build_engine(cell, SEED)
    assert engine.bucket_table is not None
    inner = engine._decode
    seen = {}

    def record(inputs):
        logits, kv = inner(inputs)
        lengths = np.asarray(inputs[-1])
        for slot, res in enumerate(engine.slot_req):
            if res is not None:
                seen.setdefault(res.uid, {})[int(lengths[slot])] = \
                    np.asarray(logits[slot])
        return logits, kv

    engine._decode = record
    for uid, p in enumerate(prompts):
        engine.submit(Request(uid=uid, tokens=p, max_new_tokens=max_new))
    results = engine.run()
    return seen, {u: results[u].output for u in results}


@pytest.fixture(scope="module")
def served():
    cell = small_cell()
    rng = np.random.default_rng(1)
    # prompt lengths that land in several buckets, none a bucket's size
    prompts = [rng.integers(0, 511, n).astype(np.int32)
               for n in (6, 13, 29, 44, 70)]
    seen, outs = _serve(cell, prompts, max_new=6)
    return cell, prompts, seen, outs


def _reference_logits(cell, prompts, outs, uid):
    fam = cell.family
    conf = cell.conf
    p, toks = prompts[uid], outs[uid]
    seq = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
    h = fam.hidden(conf, SEED, [seq], conf["engine"]["cache_len"])[0]
    pos = np.arange(len(p) - 1, len(p) - 1 + len(toks))
    rows = h[jnp.asarray(pos)]
    return pos, np.concatenate([np.asarray(lg) for _, lg in
                                fam.head_blocks(conf, SEED, rows)])


def test_engine_logits_match_the_plain_reference(served):
    cell, prompts, seen, outs = served
    vocab = cell.conf["vocab_size"]
    for uid in range(len(prompts)):
        pos, want = _reference_logits(cell, prompts, outs, uid)
        got = np.stack([seen[uid][int(q)][:vocab] for q in pos])
        assert np.abs(want).max() > 0.1
        np.testing.assert_allclose(got, want, rtol=0, atol=LOGIT_TOL)


def test_renormalised_reference_disagrees(served):
    """The check can tell the published routing from the renormalised
    one: a reference that renormalises the top-3 weights misses the
    engine's logits by far more than the tolerance."""
    cell, prompts, seen, outs = served
    cell.conf["norm_topk_prob"] = True
    try:
        pos, want = _reference_logits(cell, prompts, outs, 3)
    finally:
        cell.conf["norm_topk_prob"] = False
    got = np.stack([seen[3][int(q)][:cell.conf["vocab_size"]]
                    for q in pos])
    assert np.abs(got - want).max() > 20 * LOGIT_TOL


def test_layer_remade_alone_is_the_stacked_one():
    cell = small_cell()
    fam, dims = cell.family, cell.dims
    params = fam.make_params(dims, SEED)
    for i in range(dims.n_layers):
        one = fam.layer(dims, SEED, i)
        whole = (jax.tree.map(lambda a: a[0], params["first_block"])
                 if i == 0 else
                 jax.tree.map(lambda a: a[i - 1], params["blocks"]))
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool((a == b).all()), one, whole))


def test_the_cell_runs_and_checks_on_the_cpu():
    """The harness's whole loop for the moe cell, one chip's worth on
    the CPU: set-up, a short open-loop window, the check."""
    cell = small_cell()
    t = cell.traffic
    t.update(rate_per_s=4.0,
             prompt_tokens=dict(t["prompt_tokens"], median=40, min=8,
                                max=100),
             output_tokens=dict(t["output_tokens"], median=8, min=2,
                                max=20))
    out = harness.run(cell, SEED, 3.0, False, t_start=time.monotonic(),
                      peaks=peaks.V5E)
    assert out["correct"] is True, out["check"].numbers
    assert out["failed"] == 0 and out["compiles_in_window"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}


def test_work_counts_the_routed_experts_only():
    cell = harness.load_cell(CELL)
    fam, m = cell.family, cell.dims
    assert (m.n_layers, m.n_experts, m.top_k, m.n_shared) == (28, 64, 6, 2)
    attn = 4 * 2048 * 2048
    expert = 3 * 2048 * 1408
    want = (28 * attn + 3 * 2048 * 10944
            + 27 * (2048 * 64 + (6 + 2) * expert))
    assert fam.active_matmul_params(m) == want == 2_409_103_360
    assert fam.decode_token_flops(m, 0) == 2 * want + 2 * 2048 * 102400
    assert fam.prefill_flops(m, 1) == 2 * want + 28 * 4 * 16 * 128
    assert fam.kv_bytes_per_token(m) == 229_376
    assert fam.weight_bytes(m) == 32_758_534_144
