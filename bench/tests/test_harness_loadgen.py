"""The traffic generator: the same seed gives the same requests, every
seed the same work in the same order with other tokens, and the open
loop's rate."""

import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, loadgen  # noqa: E402

SEED = 2**31 + 12345      # seeds reach past 32 signed bits


def _load(name):
    return harness.load_cell(name).traffic


def _sizes(items):
    return (Counter(len(it.prompt) for it in items),
            Counter(it.max_new for it in items))


@pytest.mark.parametrize("cell", ["phi4-mini.chat", "phi4-mini.decode-long"])
def test_same_seed_same_requests(cell):
    traffic = _load(cell)
    a = loadgen.plan(traffic, SEED, 50, 200064)
    b = loadgen.plan(traffic, SEED, 50, 200064)
    assert [(it.uid, it.due, it.max_new, it.client) for it in a] == \
        [(it.uid, it.due, it.max_new, it.client) for it in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("cell", ["phi4-mini.chat", "phi4-mini.decode-long"])
def test_other_seed_same_work_other_tokens(cell):
    traffic = _load(cell)
    a = loadgen.plan(traffic, 1, 50, 200064)
    b = loadgen.plan(traffic, 2**31 + 77, 50, 200064)
    assert [(len(it.prompt), it.max_new, it.due, it.client) for it in a] \
        == [(len(it.prompt), it.max_new, it.due, it.client) for it in b]
    assert not any(np.array_equal(x.prompt[:8], y.prompt[:8])
                   for x, y in zip(a, b))


@pytest.mark.parametrize("cell", ["phi4-mini.chat", "phi4-mini.decode-long"])
def test_the_arrangement_orders_the_work(cell):
    traffic = _load(cell)
    a = loadgen.plan(traffic, 1, 50, 200064)
    b = loadgen.plan(dict(traffic, arrangement=traffic["arrangement"] + 1),
                     1, 50, 200064)
    assert _sizes(a) == _sizes(b)
    assert [len(it.prompt) for it in a] != [len(it.prompt) for it in b]


def test_open_loop_rate_and_window():
    traffic = dict(_load("phi4-mini.chat"), rate_per_s=2.5)
    items = loadgen.plan(traffic, 7, 40, 200064)
    assert len(items) == 100                     # rate x seconds
    due = np.array([it.due for it in items])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)
    assert due[-1] < 40                           # every request is due
    assert 0.9 / 2.5 < np.diff(due).mean() < 1.1 / 2.5
    assert len(set(np.round(np.diff(due), 9))) > 50   # not a fixed period


def test_sizes_follow_the_mix():
    t = _load("phi4-mini.chat")
    items = loadgen.plan(t, 3, 400, 200064)
    p = np.array([len(it.prompt) for it in items])
    o = np.array([it.max_new for it in items])
    assert p.min() >= t["prompt_tokens"]["min"]
    assert p.max() <= t["prompt_tokens"]["max"]
    assert abs(np.median(p) - t["prompt_tokens"]["median"]) <= 2
    assert abs(np.median(o) - t["output_tokens"]["median"]) <= 2
    assert o.min() >= t["output_tokens"]["min"]
    # the end-of-sequence id never appears in a prompt
    assert max(it.prompt.max() for it in items) < 200063


def test_closed_loop_turns_and_residual():
    t = _load("phi4-mini.decode-long")
    items = loadgen.plan(t, 5, 50, 200064)
    c = t["clients"]
    assert len(items) == c * t["requests_per_client"]
    first = items[:c]
    assert sorted(it.client for it in first) == list(range(c))
    # first turn asks for a residual share of an answer: shorter than
    # the mix's answers, down to a few tokens
    assert min(it.max_new for it in first) < t["output_tokens"]["min"]
    for it in items[c:]:
        assert t["output_tokens"]["min"] <= it.max_new \
            <= t["output_tokens"]["max"]
        assert t["prompt_tokens"]["min"] <= len(it.prompt) \
            <= t["prompt_tokens"]["max"]
    # each turn holds the same sizes in every arrangement
    other = loadgen.plan(dict(t, arrangement=t["arrangement"] + 1), 5, 50,
                         200064)
    for turn in range(t["requests_per_client"]):
        sl = slice(turn * c, (turn + 1) * c)
        assert _sizes(items[sl]) == _sizes(other[sl])
