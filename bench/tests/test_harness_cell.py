"""One cell's whole loop on the CPU at a reduced size, steered from the
test (the harness's look for a chip is skipped): set-up, the open-loop
window through ``StreamingServer``, and the check against the plain
reference."""

import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, peaks  # noqa: E402
from bench.tests._cells import reduced_cell  # noqa: E402


@pytest.fixture(scope="module")
def chat_run():
    cell = reduced_cell("phi4-mini.chat")
    return cell, harness.run(cell, 2**31 + 77, 4.0, False,
                             t_start=time.monotonic(), peaks=peaks.V5E)


def test_run_is_correct_and_reports_its_metrics(chat_run):
    cell, out = chat_run
    assert out["correct"] is True
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["attempted"] == 16 and out["failed"] == 0
    nums = out["check"].numbers
    assert nums["requests_compared"]["value"] >= 2
    assert nums["widest_gap"]["value"] <= nums["widest_gap"]["limit"]


def test_nothing_compiles_in_the_window(chat_run):
    _, out = chat_run
    assert out["compiles_in_window"] == 0


def test_every_request_is_timed_from_when_it_was_due(chat_run):
    _, out = chat_run
    rec = out["record"]
    assert [r.uid for r in rec.requests] == list(range(16))
    for r in rec.requests:
        assert r.submitted >= r.due
        assert r.recv == sorted(r.recv)
        assert not r.done or len(r.tokens) == r.max_new
    assert sum(s.decoded for s in rec.steps) > 0


def test_a_cell_on_two_chips_runs_one_sharded_engine():
    """A cell that asks for two chips gets one engine over a two-way
    model mesh, its weights made straight into their shardings (two
    forced host devices stand for the chips)."""
    script = textwrap.dedent(f"""
        import json, sys, time
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
        import jax
        from bench import harness, peaks
        from bench.tests._cells import reduced_cell
        cell = reduced_cell("phi4-mini.chat")
        cell.chips = 2
        seen = {{}}
        def look(engine):
            leaf = engine.params["blocks"]["attn"]["wk"]
            seen["devices"] = len(leaf.sharding.device_set)
            seen["shard_bytes"] = leaf.addressable_shards[0].data.nbytes
            seen["bytes"] = leaf.nbytes
        out = harness.run(cell, 2**31 + 9, 3.0, False,
                          t_start=time.monotonic(), peaks=peaks.V5E,
                          tamper=look)
        print(json.dumps({{"correct": out["correct"], **seen,
                           "gap": out["check"].numbers["widest_gap"]}}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["devices"] == 2
    assert line["shard_bytes"] * 2 == line["bytes"]
    assert line["correct"] is True, line["gap"]
