"""Roofline costing: jaxpr FLOP/byte counter and HLO collective parser."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.costing import (cost_of, hlo_collective_bytes,
                                  jaxpr_cost)


def test_dot_flops_exact():
    def f(a, b):
        return a @ b
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    c = cost_of(f, a, b)
    assert c["flops"] == 2 * 64 * 128 * 32
    assert c["bytes"] == (64 * 128 + 128 * 32 + 64 * 32) * 4


def test_scan_trip_count_multiplies():
    def f(x):
        def body(carry, _):
            return carry @ carry, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    c = cost_of(f, x)
    assert c["flops"] == 7 * 2 * 16 * 16 * 16


def test_grad_includes_remat_recompute():
    def layer(x, w):
        return jnp.tanh(x @ w)

    def loss_plain(x, w):
        return jnp.sum(layer(x, w))

    def loss_remat(x, w):
        return jnp.sum(jax.checkpoint(layer)(x, w))

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    plain = cost_of(jax.grad(loss_plain, argnums=1), x, w)
    remat = cost_of(jax.grad(loss_remat, argnums=1), x, w)
    assert remat["flops"] > plain["flops"], \
        "remat recompute must be visible to the counter"


def test_hlo_collective_parser_with_while_trips():
    hlo = """
body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %ar = f32[8]{0} all-reduce(%x), replica_groups={}
  ROOT %t = (s32[], f32[8]) tuple(%i, %ar)
}

cond.1 (p: (s32[], f32[8])) -> pred[] {
  %c = s32[] constant(5)
  ROOT %cmp = pred[] compare(%i, %c), direction=LT
}

ENTRY main.1 (a: f32[8]) -> f32[8] {
  %ag = f32[16]{0} all-gather(%a), dimensions={0}
  %w = (s32[], f32[8]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[8] get-tuple-element(%w), index=1
}
"""
    out = hlo_collective_bytes(hlo)
    # all-gather once: 16*4 = 64 B; all-reduce 5 trips x 8*4 x2 (ring) = 320.
    assert out["all-gather"] == 64
    assert out["all-reduce"] == 5 * 32 * 2
    assert out["total"] == 64 + 320


def test_f32_as_bf16_equivalence_mode():
    hlo = """
ENTRY main.1 (a: f32[8]) -> f32[8] {
  %ag = f32[16]{0} all-gather(%a), dimensions={0}
  ROOT %r = f32[8] slice(%ag)
}
"""
    raw = hlo_collective_bytes(hlo)
    eq = hlo_collective_bytes(hlo, f32_as_bf16=True)
    assert raw["all-gather"] == 64 and eq["all-gather"] == 32


def test_shard_map_scaled_by_mesh():
    import os
    if len(jax.devices()) < 1:
        return
    from jax.sharding import AxisType, PartitionSpec as P

    mesh = jax.make_mesh((1,), ("m",), axis_types=(AxisType.Auto,))

    def f(x):
        return jax.shard_map(lambda v: v @ v, mesh=mesh,
                             in_specs=P(None, None), out_specs=P(None, None),
                             check_vma=False)(x)
    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    c = cost_of(f, x)
    assert c["flops"] == 2 * 8 * 8 * 8 * mesh.size
