"""``bench/run.py`` refuses to measure where it cannot: no TPU, a
checkout that holds only the benchmark's own files, or a metric of the
cell that read nothing."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench import run as bench_run  # noqa: E402

ARGS = ["--workload", "phi4-mini.chat", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode not in (0, None)
    assert not _has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)


def test_a_metric_that_read_nothing_is_named():
    cell = harness.load_cell("phi4-mini.chat")
    per_layer = {m["name"]: {"value": 1.0} for m in cell.per_layer}
    assert bench_run.unread(cell, per_layer, True) == []
    del per_layer["decode_attn_roofline"]
    assert bench_run.unread(cell, per_layer, True) == \
        ["decode_attn_roofline"]
    assert bench_run.unread(cell, {}, False) == \
        [m["name"] for m in cell.end_to_end]
