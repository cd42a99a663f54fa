"""The main path's Pallas kernels, compiled at real widths for a
described TPU v5e (``interpret=False``).  Nothing runs: the TPU compiler
checks tiling, fast-memory use and lowering, which interpret mode cannot.
Each compiled program must hold the kernel (``tpu_custom_call``)."""

import dataclasses
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described ``v5e:2x2``, with the persistent
    compilation cache off (such compiles cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for(one_chip, monkeypatch):
    """``compile_for(fn, *shapes)`` -> compiled HLO text, with the
    wrappers' kernels compiled as on the chip."""
    monkeypatch.setattr(ops, "interpret_mode", lambda backend=None: False)

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    return run


BF16 = jnp.bfloat16
# phi4-mini-3.8b: 24 query heads, 8 KV heads, head_dim 128
B, H, KH, D = 4, 24, 8, 128


def test_flash_decode_phi4_mini(compile_for):
    hlo = compile_for(ops.decode_attention, ((B, H, D), BF16),
                      ((B, KH, 512, D), BF16), ((B, KH, 512, D), BF16),
                      ((B,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_paged_decode_block_128(compile_for):
    pool = (B * 4 + 1, KH, 128, D)
    hlo = compile_for(ops.paged_decode_attention, ((B, H, D), BF16),
                      (pool, BF16), (pool, BF16), ((B, 4), jnp.int32),
                      ((B,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_quant_matmul_vww_fc(compile_for):
    """The VWW classifier: (1, 256) int8 activations x (256, 2), under
    ``jax.enable_x64`` as the micro invoke traces it."""
    def fc(x, w, bias, scale):
        return ops.quant_matmul(x, w, bias, 3, scale, -5)
    with jax.enable_x64(True):
        hlo = compile_for(fc, ((1, 256), jnp.int8), ((256, 2), jnp.int8),
                          ((2,), jnp.int32), ((2,), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_dequant_matmul_int8_3072x8192(compile_for):
    def mm(x, q8, qs):
        return ops.dequant_matmul(x, {"q8": q8, "qs": qs})
    hlo = compile_for(mm, ((B, 3072), jnp.float32),
                      ((3072, 8192), jnp.int8), ((8192,), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_flash_attention_prefill_phi4_mini(compile_for):
    hlo = compile_for(lambda q, k, v: ops.flash_attention(q, k, v),
                      ((1, H, 512, D), BF16), ((1, KH, 512, D), BF16),
                      ((1, KH, 512, D), BF16))
    assert "tpu_custom_call" in hlo


def _hlo_types(hlo):
    """{instruction name: (result type, opcode, operand names)}."""
    out = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*)", line)
        if not m:
            continue
        rest, depth = m.group(2), 0
        for end, ch in enumerate(rest):
            depth += ch in "([{"
            depth -= ch in ")]}"
            if ch == " " and depth == 0:
                break
        op = re.match(r" ([\w-]+)\(([^)]*)\)", rest[end:])
        if op:
            args = re.findall(r"%([\w.-]+)", op.group(2))
            out[m.group(1)] = (rest[:end], op.group(1), args)
    return out


def test_decode_step_updates_the_cache_in_place(one_chip, monkeypatch):
    """The engine's contiguous decode step at phi4-mini widths (4
    layers, 8 slots x 2048, Pallas attention) takes the donated cache
    as its output buffer, needs next to no scratch, and writes each
    slot's new row in place: nothing else produces a whole cache."""
    from repro.configs import get_config
    from repro.core.op_resolver import MicroMutableOpResolver
    from repro.core.schema import OpCode, OpDef
    from repro.models import get_model
    from repro.serving import DEFAULT_TAGS
    from repro.serving import ops as serving_ops
    from repro.serving.engine import DecodeStep

    monkeypatch.setattr(ops, "interpret_mode", lambda backend=None: False)
    cfg = dataclasses.replace(get_config("phi4-mini-3.8b"), n_layers=4)
    bundle = get_model(cfg)
    code = OpCode.SERVING_DECODE
    reg = MicroMutableOpResolver(DEFAULT_TAGS).add_many([code]).resolve(code)
    op = OpDef(code, (), (), params={"window": cfg.sliding_window})
    ctx = serving_ops.ServingContext(bundle)
    ctx = serving_ops.ServingContext(bundle, reg.prepare(ctx, op).op_data)
    step = DecodeStep(functools.partial(reg.eval, ctx, op))

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    params = jax.tree.map(lambda a: shape(a.shape, a.dtype),
                          jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    ring = (cfg.n_layers, 8, cfg.n_kv_heads, 2048, cfg.dh)
    cache = {kk: shape(ring, BF16) for kk in ("k", "v")}
    compiled = step.lower((params, cache, shape((8, 1), jnp.int32),
                           shape((8,), jnp.int32))).compile()

    cache_bytes = 2 * math.prod(ring) * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 100
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    whole = "bf16[%s]" % ",".join(map(str, ring))
    row = "bf16[1,1,%d,1,%d]" % (cfg.n_kv_heads, cfg.dh)
    types = _hlo_types(hlo)
    writes = 0
    for name, (typ, opcode, args) in types.items():
        if whole not in typ or opcode in ("parameter", "get-tuple-element",
                                          "tuple", "while", "bitcast"):
            continue
        assert opcode == "dynamic-update-slice", (name, opcode)
        assert types[args[1]][0].startswith(row), (name, types[args[1]])
        writes += 1
    assert writes == 2 * 8          # K and V of every slot, per layer
