"""shard_map expert-parallel MoE vs the GSPMD einsum-dispatch path.

With a dropless capacity factor the two implementations compute the
same math (same routing, same experts), so outputs must agree.  Runs
in a subprocess with 8 placeholder devices (2 data x 4 model)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import lm as L
    from repro.distributed.act_sharding import activation_sharding
    from repro.launch.mesh import make_mesh

    cfg = get_config("qwen3-moe-30b-a3b", reduced=True)
    cfg = dataclasses.replace(cfg, n_experts=8, top_k=2,
                              capacity_factor=8.0)   # dropless
    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    p = {
        "router": jax.random.normal(key, (d, e), jnp.float32) * 0.1,
        "experts": {
            "wi": jax.random.normal(key, (e, d, f)) * 0.05,
            "wg": jax.random.normal(jax.random.fold_in(key, 1),
                                    (e, d, f)) * 0.05,
            "wo": jax.random.normal(jax.random.fold_in(key, 2),
                                    (e, f, d)) * 0.05,
        },
    }
    x = jax.random.normal(jax.random.fold_in(key, 3), (4, 16, d))

    # reference: GSPMD path, single group (no ctx -> ep not applicable)
    y_ref, aux_ref = jax.jit(
        lambda p, x: L.moe_block(p, cfg, x, data_shards=1, capped=True))(p, x)

    # EP path under the mesh ctx
    def ep(p, x):
        with activation_sharding(mesh, batch_divisible=True,
                                 seq_divisible=True,
                                 experts_divisible=True):
            from repro.models.moe_ep import ep_applicable, moe_block_ep
            assert ep_applicable(cfg, x.shape[0], x.shape[1])
            return moe_block_ep(p, cfg, x)

    with mesh:
        y_ep, aux_ep = jax.jit(ep)(p, x)

    err = float(jnp.max(jnp.abs(y_ref - y_ep)))
    rel = err / float(jnp.max(jnp.abs(y_ref)))
    print("RESULT" + json.dumps({"max_err": err, "rel": rel,
                                 "aux_ref": float(aux_ref),
                                 "aux_ep": float(aux_ep)}))
""")


@pytest.mark.slow
def test_moe_ep_matches_gspmd_path():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT")][0]
    out = json.loads(line[len("RESULT"):])
    # expert outputs must agree to numerical precision (dropless)
    assert out["rel"] < 1e-4, out
    # aux load-balance is a per-device density estimator under EP vs a
    # global one under GSPMD — same scale, not bit-equal
    assert abs(out["aux_ref"] - out["aux_ep"]) < 0.6, out
