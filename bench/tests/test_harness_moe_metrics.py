"""The readers that the moe cell adds, on records made by hand:
``collective_share`` from a trace summary, ``expert_pad_share`` from the
engine's per-tick row counters."""

import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, peaks  # noqa: E402
from bench.trace_reduce import Summary  # noqa: E402

CELL = "deepseek-moe-16b.chat.4chip"


def _rec(steps=(), trace=None):
    return harness.RunRecord(cell=harness.load_cell(CELL), seconds=10.0,
                             setup_s=1.0, max_slots=16, peaks=peaks.V5E,
                             requests=[], steps=list(steps),
                             compiles_in_window=0, trace=trace)


def _summary(ops, busy_s=2.0, devices=4):
    return Summary(window_s=5.0, busy_s=busy_s, devices=devices, ops=ops,
                   modules={}, gaps=[])


def test_collective_share_sums_every_collective_over_the_chips():
    ops = {"all-reduce.3": 0.4, "all-reduce-start.7": 0.1,
           "all-reduce-done.7": 0.05, "all-gather.2": 0.2,
           "reduce-scatter.1": 0.05, "all-to-all.4": 0.1,
           "collective-permute-done.9": 0.1,
           "fusion.12": 5.0, "decode_attention_pallas.3": 1.0,
           # an op that only mentions a collective is not one
           "fusion.all-reduce.2": 9.0}
    share = harness.reader("collective_share")(_rec(trace=_summary(ops)))
    # 1.0 s of collectives on four chips each busy 2.0 s
    assert share == pytest.approx(100.0 * 1.0 / 8.0)


def test_collective_share_reads_nothing_without_collectives():
    read = harness.reader("collective_share")
    assert read(_rec()) is None
    assert read(_rec(trace=_summary({"fusion.1": 1.0}, devices=1))) is None


@dataclasses.dataclass
class _Tick(harness.Step):
    expert_rows: int = 0
    routed_rows: int = 0


def test_expert_pad_share_counts_the_rows_no_token_was_routed_to():
    # a 16-slot decode tick with 10 live slots over 27 MoE layers of 64
    # experts, top 6; a 512-token prefill bucket holding 300 tokens
    decode = _Tick(1.0, 1.02, 0, True, 10, expert_rows=27 * 64 * 16,
                   routed_rows=27 * 6 * 10)
    prefill = _Tick(2.0, 2.1, 1, True, 11, expert_rows=27 * 64 * 512,
                    routed_rows=27 * 6 * 300)
    late = _Tick(11.0, 11.1, 0, True, 11, expert_rows=1, routed_rows=0)
    rec = _rec(steps=[decode, prefill, late])
    want = 100.0 * (1 - (6 * 10 + 6 * 300) / (64 * 16 + 64 * 512))
    assert harness.reader("expert_pad_share")(rec) == pytest.approx(want)


def test_expert_pad_share_reads_nothing_without_the_counters():
    read = harness.reader("expert_pad_share")
    assert read(_rec(steps=[harness.Step(1.0, 1.1, 0, True, 4)])) is None
    assert read(_rec(steps=[_Tick(1.0, 1.1, 0, True, 4)])) is None
