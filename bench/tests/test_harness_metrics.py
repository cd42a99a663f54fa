"""The arithmetic of the end-to-end and per-layer readers, and of
``bench/work.py``, on records made by hand."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, peaks, stats, work  # noqa: E402
from bench.trace_reduce import Summary  # noqa: E402


def _req(uid, due, recv, plen=10, max_new=None, done=True):
    return harness.Req(uid=uid, prompt=np.zeros(plen, np.int32),
                       max_new=max_new or len(recv), due=due,
                       submitted=due, recv=list(recv),
                       tokens=[1] * len(recv), done=done)


def _rec(requests, seconds=10.0, steps=(), trace=None, t0=0.0, t1=0.0):
    cell = harness.load_cell("phi4-mini.chat")
    return harness.RunRecord(cell=cell, seconds=seconds, setup_s=1.5,
                             max_slots=8, peaks=peaks.V5E,
                             requests=list(requests), steps=list(steps),
                             compiles_in_window=0, trace=trace,
                             trace_t0=t0, trace_t1=t1)


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 95) == 95
    assert stats.percentile([5.0], 99) == 5.0
    assert stats.percentile([], 50) is None
    assert stats.percentile([3, 1, 2], 50) == 2


def test_ttft_counts_unfinished_requests_at_their_wait():
    reqs = [_req(i, due=0.1 * i, recv=[0.1 * i + 0.05]) for i in range(9)]
    # due at 5 s, still without a token when the 10 s window closes
    reqs.append(_req(9, due=5.0, recv=[], done=False))
    # due after the window: not counted
    reqs.append(_req(10, due=10.5, recv=[10.6]))
    ttft = harness.reader("ttft_p90_ms")(_rec(reqs))
    assert ttft == pytest.approx(50.0)          # 9 of 10 waited 50 ms
    reqs[9] = _req(9, due=5.0, recv=[], done=False)
    reqs += [_req(11, due=4.0, recv=[], done=False)]
    # two of 11 wait 5 s and 6 s: the 90th percentile is the shorter
    assert harness.reader("ttft_p90_ms")(_rec(reqs)) == \
        pytest.approx(5000.0)


def test_itl_takes_gaps_inside_the_window_only():
    reqs = [_req(0, 0.0, [1.0, 1.02, 1.04, 1.10]),
            _req(1, 0.0, [9.98, 10.5])]          # second token after close
    rec = _rec(reqs)
    # gaps: 20, 20, 60 ms; the 9.98 -> 10.5 gap ends outside the window
    assert harness.reader("itl_p95_ms")(rec) == pytest.approx(60.0)
    assert harness.reader("output_tokens_per_s")(rec) == \
        pytest.approx(5 / 10.0)


def test_generator_lag_and_occupancy():
    reqs = [_req(i, due=float(i), recv=[i + 0.1]) for i in range(5)]
    for i, r in enumerate(reqs):
        r.submitted = r.due + 0.001 * i
    rec = _rec(reqs, steps=[harness.Step(1.0, 1.02, 0, True, 4),
                            harness.Step(1.02, 1.04, 1, True, 8),
                            harness.Step(1.04, 1.05, 1, False, 8),
                            harness.Step(11.0, 11.1, 0, True, 0)])
    assert harness.reader("gen_lag_p99_ms")(rec) == pytest.approx(4.0)
    assert harness.reader("batch_occupancy")(rec) == pytest.approx(75.0)


def test_phi4_bytes_from_its_configuration():
    m = harness.load_cell("phi4-mini.chat").dims
    assert work.kv_bytes_per_token(m) == 32 * 2 * 8 * 128 * 2
    assert work.weight_bytes(m) == 7_675_975_680
    assert work.layer_matmul_params(m) == 100_663_296


def test_decode_attention_bytes_follow_live_length():
    m = harness.load_cell("phi4-mini.chat").dims
    per_pos = work.kv_bytes_per_token(m)
    qo = 32 * 2 * 24 * 128 * 2
    assert work.decode_attn_bytes(m, 1) == per_pos + qo
    assert work.decode_attn_bytes(m, 1300) == 1300 * per_pos + qo
    # the roofline time of one query at 1300 positions is memory-bound
    t = work.roofline_s(work.attention_flops(m, 1300),
                        work.decode_attn_bytes(m, 1300),
                        peaks.V5E.bf16_flops, peaks.V5E.hbm_bw)
    assert t == pytest.approx(work.decode_attn_bytes(m, 1300) / 819e9)


def test_flops_of_prefill_and_decode():
    m = harness.load_cell("phi4-mini.chat").dims
    dense = 2 * 32 * 100_663_296
    assert work.decode_token_flops(m, 0) == dense + 2 * 3072 * 200064
    assert work.prefill_flops(m, 4) == 4 * dense + 32 * 4 * 24 * 128 * 10


def test_step_mfu_and_idle_share_from_a_summary():
    s = Summary(window_s=2.0, busy_s=1.5, devices=1, ops={}, modules={},
                gaps=[])
    reqs = [_req(0, 0.0, [0.5, 0.6], plen=100)]
    rec = _rec(reqs, trace=s, t0=0.4, t1=2.4)
    m = rec.dims
    flops = (work.decode_token_flops(m, 100) + work.decode_token_flops(m, 101)
             + work.prefill_flops(m, 99))
    assert harness.reader("step_mfu")(rec) == \
        pytest.approx(100 * flops / (2.0 * 197e12))
    assert harness.reader("device_idle_share")(rec) == pytest.approx(25.0)
    assert harness.reader("step_mfu")(_rec(reqs)) is None


def test_unknown_device_kind_is_an_error():
    assert peaks.for_kind("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v4")
