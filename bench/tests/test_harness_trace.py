"""The reduction from a profiler trace to busy time, idle gaps and time
per operation, on a trace recorded here on the CPU (whose XLA ops run
on host threads, so the test names those lines as the device's)."""

import glob
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402

CPU = dict(device_plane=r"^/host:CPU$", op_line=r"^tf_XLAPjRtCpuClient",
           module_line=r"^$")


def test_union_complement_label():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (4, 5)]) == [(0, 2), (3, 5)]
    busy = tr.union([(1, 2), (3, 4)])
    assert tr.complement(busy, 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert tr.complement(busy, 1.5, 3.5) == [(2, 3)]
    spans = [("engine.step", 1.9, 2.8), ("server.submit", 2.8, 3.0),
             ("client.receive", 3.9, 4.2)]
    assert tr.label((2, 3), spans) == "engine.step"
    assert tr.label((4, 5), spans) == tr.NO_SPAN
    assert tr.clip((0, 10), 2, 3) == (2, 3) and tr.clip((0, 1), 2, 3) is None


def test_program_ids_are_dropped_from_module_names():
    import re
    for name in ("jit_eval(8543123)", "jit_eval (12)", "jit_eval"):
        assert re.sub(tr.PROGRAM_ID, "", name) == "jit_eval"
    assert re.sub(tr.PROGRAM_ID, "", "jit_f(x)") == "jit_f(x)"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    d = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("engine.step"):
                f(x).block_until_ready()
            time.sleep(0.02)          # the device idles here
    jax.profiler.stop_trace()
    return glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]


def test_reduce_a_recorded_trace(recorded):
    s = tr.reduce(recorded, spans=("engine.step",), **CPU)
    assert s.devices == 1
    assert 0.06 <= s.window_s < 5.0
    assert 0 < s.busy_s < s.window_s
    assert any("dot" in name for name in s.ops)
    idle = s.idle_by_span()
    assert sum(t for _, t in idle.values()) == \
        pytest.approx(s.window_s - s.busy_s)
    # the three sleeps are the longest gaps, outside any step
    longest = sorted(s.gaps, key=lambda g: -g[1])[:3]
    assert all(name == tr.NO_SPAN and t >= 0.015 for name, t in longest)


def test_reduce_refuses_a_trace_without_its_window(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    jax.numpy.ones(3).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce(path, **CPU)
