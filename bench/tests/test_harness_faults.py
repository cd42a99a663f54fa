"""The check that decides ``correct`` fails what it must: the float8
control in the program's place, and a timed path broken underneath
(a token altered where it is produced; a decode step that returns its
cache unchanged).  Each drives a whole run on the CPU at a reduced
size, the harness's look for a chip skipped."""

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, peaks  # noqa: E402
from bench.tests._cells import reduced_cell  # noqa: E402

SEED = 31


def _run(cell_name, tamper=None, control=False):
    cell = reduced_cell(cell_name)
    return harness.run(cell, SEED, 4.0, False, t_start=time.monotonic(),
                       peaks=peaks.V5E, tamper=tamper, control=control)


def _alter_tokens(engine):
    """Every sampled token moved to the next id, on the device, where
    the overlapped loop produces it."""
    argmax, vocab = engine._argmax, engine.cfg.vocab
    engine._argmax = lambda logits: (argmax(logits) + 1) % vocab


def _keep_cache(engine):
    """The decode step hands back the cache it was given: no position
    it decodes is ever written."""
    decode = engine._decode

    def step(inputs):
        logits, _ = decode(inputs)
        return logits, inputs[1]

    engine._decode = step


@pytest.mark.parametrize("cell_name", ["phi4-mini.chat",
                                       "phi4-mini.decode-long"])
@pytest.mark.parametrize("fault", [_alter_tokens, _keep_cache],
                         ids=["token_altered", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(cell_name, fault):
    out = _run(cell_name, tamper=fault)
    gap = out["check"].numbers["widest_gap"]
    assert out["correct"] is False
    assert gap["value"] > gap["limit"]


def test_the_float8_control_is_not_correct():
    out = _run("phi4-mini.decode-long", control=True)
    gap = out["check"].numbers["widest_gap"]
    assert out["correct"] is False
    assert gap["value"] > gap["limit"]
    assert out["check"].other["program_widest_gap"] <= gap["limit"]
