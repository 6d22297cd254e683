"""shard_map MoE dispatch == dense oracle path, on a real multi-device mesh.

Runs in a subprocess so the 8 fake host devices don't leak into other
tests' jax runtime.
"""

import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, dataclasses
    from repro import configs
    from repro.models import moe
    from repro.runtime.sharding import sharding_context, Rules

    cfg = dataclasses.replace(configs.get_smoke('olmoe_1b_7b'),
                              moe_capacity_factor=8.0)
    key = jax.random.PRNGKey(0)
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff
    params = {k2: jax.random.normal(jax.random.fold_in(key, i), s) * 0.05
              for i, (k2, s) in enumerate([
                  ('router', (d, e)), ('w_gate', (e, d, f)),
                  ('w_up', (e, d, f)), ('w_down', (e, f, d))])}
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, d)) * 0.5
    y_dense, _ = moe._moe_ffn_dense(params, x, cfg)
    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with sharding_context(mesh, Rules(batch=("data",), expert=("model",))):
        y_sm, _ = jax.jit(lambda p, xx: moe.moe_ffn(p, xx, cfg))(params, x)
    err = float(jnp.max(jnp.abs(y_dense - y_sm)))
    assert err < 1e-6, err
    # Gradients flow through the shard_map dispatch.
    def loss(p):
        with sharding_context(mesh, Rules(batch=("data",),
                                          expert=("model",))):
            y, aux = moe.moe_ffn(p, x, cfg)
        return jnp.sum(jnp.square(y)) + aux
    g = jax.grad(loss)(params)
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in g.values())
    print("MOE_SHARDMAP_OK", err)
""")


@pytest.mark.slow
def test_moe_shardmap_equivalence_subprocess():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=600, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
        | __import__("os").environ.copy() | {"PYTHONPATH": "src"})
    assert "MOE_SHARDMAP_OK" in out.stdout, out.stderr[-2000:]
