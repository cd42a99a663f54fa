"""``chip_smoke.py`` rehearsed on the CPU: each phase at reduced
configs (the script itself always runs the published ones), the
sharded phase on four forced host devices, and the refusal to report a
result without a TPU."""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load()


def test_micro_phase(smoke, capsys):
    smoke.micro_phase(batch=2)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "micro"
    assert line["max_lsb"] <= smoke.MICRO_LSB
    assert line["pool_max_lsb"] <= smoke.MICRO_LSB


def test_serve_phase_reduced(smoke, capsys):
    smoke.serve_phase(get_config("phi4-mini-3.8b", reduced=True),
                      cache_len=64, prompt_lens=(9, 30), max_new=6,
                      kv_block=16)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["tokens_generated"] == 8 * 6
    assert line["paged_tokens_identical"]
    assert line["kv_in_place"] == [True, True]
    assert not line["decode_hlo_has_tpu_custom_call"]   # interpreted


def test_sharded_phase_four_host_devices():
    script = textwrap.dedent(f"""
        import dataclasses, importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.configs import get_config
        cfg = dataclasses.replace(
            get_config("deepseek-moe-16b", reduced=True), dtype="bfloat16")
        cs.sharded_phase(cfg, 4, cache_len=64, prompt_len=20, max_new=4)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["phase"] == "sharded" and line["chips"] == 4
    assert max(line["param_bytes_per_device"]) < line["param_bytes"]
    assert line["first_decode_vs_forward_rel_err"] <= line["rel_tol"]
    assert line["kv_in_place"]


def test_no_result_without_tpu(smoke, capsys):
    with pytest.raises(RuntimeError, match="no TPU"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out
