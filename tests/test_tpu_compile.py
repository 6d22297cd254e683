"""Compile the batched engine's kernels for a described TPU v5e, no chip.

The TPU compiler is installed even where no chip is attached, and it
compiles for a chip that is only described (``topologies.get_topology_desc``).
What it refuses here -- an op it cannot lower, a program that does not fit
-- it would refuse on the chip, so these compiles guard the main path at
no chip time.  Everything is float64 at the smoke grid's widths: 64-host
clusters (the vSphere 6.x per-cluster maximum) at 10 VMs per host, i.e.
16 dense VM slots per host.  Nothing runs, so nothing here says anything
about results or speed.

This is the only test file that describes the chip.  The topology is built
inside a module-scoped fixture (never at import time): only one process at
a time may load the TPU library, and under pytest-xdist every worker
imports every test file, so only the worker given this file may touch it.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import backend as backend_mod
from repro.core import kernels
from repro.drs.entitlement import waterfill_dense

S, H, J = 8, 64, 16           # cells, hosts per cell, VM slots per host
ITERS = 100                   # BatchedSimulator's waterfill_iters


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    """No persistent compilation cache around a described-chip compile: an
    entry written for the TPU cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    """AOT-compile ``fn`` in float64 on the plain ``jax`` executor."""
    with jax.enable_x64(True), backend_mod.executor_scope("jax"):
        compiled = jax.jit(fn).lower(*shapes).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    return compiled


def _f64(shape, sharding, dtype=jnp.float64):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _demand_gathers(hlo):
    """Lines of optimised HLO built for a gather traced in ``repro/demand``.

    The TPU's float64 rewrite renames the ``gather`` instruction's own
    ``op_name`` to a bare ``gather``; the index packing and the fusion
    around it keep the traced name, ``.../repro/demand/.../gather``."""
    return [line for line in hlo.splitlines()
            if re.search(r'op_name="[^"]*repro/demand/[^"]*gather', line)]


def test_waterfill_dense_compiles_for_v5e(one_chip, no_cache):
    be = backend_mod.jax_backend()

    def wf(capacity, floors, ceils, weights, active):
        return waterfill_dense(jnp, be.fori, capacity, floors, ceils,
                               weights, iters=ITERS, active=active)

    slots = _f64((S, H, J), one_chip)
    compiled = _compile(wf, _f64((S, H), one_chip), slots, slots, slots,
                        _f64((S, H, J), one_chip, jnp.bool_))
    assert "f64" in compiled.as_text()


def test_balance_caps_compiles_for_v5e(one_chip, no_cache):
    be = backend_mod.jax_backend()

    def balance(on, idle, peak, cap_peak, hyp, caps, floors, ceils,
                weights, active, cpu_reserved, budget, enabled):
        hosts = kernels.HostCols(on, idle, peak, cap_peak, hyp)

        def ents_at(c):
            managed = kernels.managed_capacity(jnp, hosts, c)
            alloc = waterfill_dense(jnp, be.fori, managed, floors, ceils,
                                    weights, iters=ITERS, active=active)
            return jnp.sum(alloc, axis=-1)

        return kernels.balance_caps(be, hosts, caps, ents_at, cpu_reserved,
                                    budget, enabled, kernels.BalanceParams())

    col = _f64((S, H), one_chip)
    slots = _f64((S, H, J), one_chip)
    _compile(balance, _f64((S, H), one_chip, jnp.bool_), col, col, col, col,
             col, slots, slots, slots, _f64((S, H, J), one_chip, jnp.bool_),
             col, _f64((S,), one_chip), _f64((S,), one_chip, jnp.bool_))


def test_demand_lookup_has_no_gather_on_v5e(one_chip, no_cache):
    """The per-tick trace lookup selects over the 3-segment axis: XLA
    lowers a gather there to a serial per-element loop on the chip."""
    from repro.sim.batch import trace_demands

    K = 3                          # segments of the spike traces
    tr = {"period": _f64((S, H, J), one_chip)}
    tr.update({c: _f64((S, H, J, K), one_chip)
               for c in ("bps", "cpu_vals", "mem_vals")})
    hlo = _compile(trace_demands, tr, _f64((), one_chip)).as_text()
    assert "repro/demand" in hlo
    assert " gather(" not in hlo
    assert not _demand_gathers(hlo)


def test_cap_only_program_compiles_for_v5e(one_chip, no_cache):
    """The whole cap-only scan of ``run_sweep(engine="batch")`` for the four
    spike x host-mix families at 64 hosts, every policy, one DRS period."""
    from repro.sim import batch as batch_mod
    from repro.sim import sweep
    from repro.sim.experiments import POLICIES

    specs = sweep.scenario_families(sizes=(H,), churns=("none",),
                                    duration_s=600.0)
    cells, _ = sweep._build_batch_cells(specs, POLICIES)
    (hp, jp), = {sweep._bucket_key(c) for c in cells}
    assert (hp, jp) == (H, J)
    sim = batch_mod.BatchedSimulator(
        cells, slot_slack=3.0, balancer=sweep._grid_balancer(specs),
        n_devices=1, pad_hosts=hp, pad_slots=jp)
    static, _, arrays, _ = sim._prepare()
    assert not static.churn and static.executor == "jax"
    shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
              for k, v in arrays.items()}
    with jax.enable_x64(True), batch_mod._quiet_donation():
        compiled = batch_mod._compiled_program(static, 1).lower(
            shapes).compile()
    # The chip holds every packed input, padded to its tiles.
    assert compiled.memory_analysis().argument_size_in_bytes >= sum(
        v.nbytes for v in arrays.values())
    hlo = compiled.as_text()
    assert "f64" in hlo
    assert "repro/demand" in hlo and not _demand_gathers(hlo)
