"""Differential harness: the Pallas executor against the lax and NumPy ones.

The contract under test (`repro.kernels.powercap`): off-TPU the Pallas
kernels run in interpret mode, where they execute the same float64 op
sequence as the lax executor and must be **bit-identical** to it --
entitlements and did-anything flags -- across random (reservation, limit,
shares, demand, budget) tuples and every degenerate regime (zero-demand
hosts, all-reserved budgets, single-VM hosts, empty hosts, budget below
the reserved floor).  BalancePowerCap caps are held to a few ULPs instead
(see :data:`BALANCE_CAPS_MAXULP`).  The NumPy executor differs from the JAX planes only
by reduction order, so it is compared at ~1 ulp-per-reduction tolerance
(1e-9 relative), not bitwise.

Fuzzing runs twice: a seed-parametrized sweep that always runs (no extra
dependencies), and hypothesis-driven fuzzing over the same problem builder
when hypothesis is installed (CI pins ``HYPOTHESIS_PROFILE=ci``:
derandomized, fixed example counts -- see ``conftest.py``).

Also locks the ``waterfill_dense`` padded-slot leak fix: poisoned padding
values in inactive slots must not absorb entitlement once the ``active``
mask is passed (regression for the pre-mask-only era, where stale demand
in recycled slots could widen the bisection bracket).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import backend as backend_mod
from repro.backend import NUMPY
from repro.core import kernels
from repro.drs.entitlement import (batched_waterfill, waterfill_core,
                                   waterfill_dense, waterfill_dense_math)
from repro.kernels.powercap import ops, ref

try:
    from hypothesis import given, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS,
    reason="hypothesis-driven fuzzing needs hypothesis (requirements.txt)")

SCENARIOS = ("plain", "zero_demand", "all_reserved", "single_vm",
             "empty_host", "budget_below_floor")
SEEDS = tuple(range(5))

#: Caps from the fused Pallas balance round vs the lax loop.  The same math
#: runs in both, but interpret mode lowers the kernel body as its own loop
#: over grid steps with ref loads and stores, a different XLA program from
#: the lax ``while_loop`` body, and XLA is free to round or contract its
#: multiply-adds differently.  On JAX 0.9.0 caps of ~100-300 W differ by up
#: to 4 ULPs (~1.1e-13 W, over 900 seeded problems); the bound leaves 2x
#: margin for hypothesis's draws.  A broken kernel is off by watts.
BALANCE_CAPS_MAXULP = 8


# ------------------------------------------------------ problem builders
def dense_problem(seed: int, scenario: str, s: int = 2, h: int = 5,
                  j: int = 6):
    """One (capacity, floors, ceils, weights, active) tuple in the dense
    slot layout, with the named degenerate regime injected."""
    rng = np.random.default_rng(seed)
    floors = rng.uniform(0.0, 300.0, (s, h, j))
    ceils = floors + rng.uniform(0.0, 500.0, (s, h, j))
    weights = rng.uniform(0.1, 10.0, (s, h, j))
    active = rng.random((s, h, j)) < 0.8
    if scenario == "zero_demand":
        # Entire hosts with zero demand (and zero reservations).
        floors[:, 0, :] = 0.0
        ceils[:, 0, :] = 0.0
    elif scenario == "all_reserved":
        # Budget fully reserved: every ceiling pinned at its floor.
        ceils = floors.copy()
    elif scenario == "single_vm":
        active[:] = False
        active[:, :, 0] = True
    elif scenario == "empty_host":
        active[:, 1, :] = False
    floors = np.where(active, floors, 0.0)
    ceils = np.where(active, ceils, 0.0)
    total_floor = floors.sum(axis=-1)
    if scenario == "budget_below_floor":
        capacity = total_floor * rng.uniform(0.1, 0.9, (s, h))
    else:
        capacity = rng.uniform(0.0, 1.2, (s, h)) * np.maximum(
            ceils.sum(axis=-1), 1.0)
    return capacity, floors, ceils, weights, active


def balance_problem(seed: int, scenario: str, s: int = 2, h: int = 5,
                    j: int = 6):
    """A BalancePowerCap cell batch around a dense entitlement problem."""
    rng = np.random.default_rng(seed ^ 0x5EED)
    _, floors, ceils, weights, active = dense_problem(seed, scenario, s, h,
                                                      j)
    on = rng.random((s, h)) < 0.85
    if scenario == "empty_host":
        on[:, 1] = True      # keep the empty host powered on
    idle = rng.uniform(80.0, 120.0, (s, h))
    peak = idle + rng.uniform(100.0, 200.0, (s, h))
    cap_peak = rng.uniform(2000.0, 4000.0, (s, h))
    hyp = rng.uniform(0.0, 50.0, (s, h))
    hosts = kernels.HostCols(on, idle, peak, cap_peak, hyp)
    caps0 = rng.uniform(idle, peak)
    managed0 = kernels.managed_capacity(np, hosts, caps0)
    cpu_res = managed0 * rng.uniform(0.0, 0.8, (s, h))
    budget = np.sum(np.where(on, caps0, 0.0), axis=-1)
    if scenario == "budget_below_floor":
        budget = budget * 0.5
    enabled = rng.random(s) < 0.9
    dense = kernels.DenseCols(floors, ceils, weights, active)
    return hosts, caps0, dense, cpu_res, budget, enabled


def segmented_problem(seed: int, scenario: str, n: int = 40,
                      n_segs: int = 7):
    rng = np.random.default_rng(seed ^ 0xCAFE)
    seg = rng.integers(0, n_segs, n)
    floors = rng.uniform(0.0, 100.0, n)
    ceils = floors + rng.uniform(0.0, 300.0, n)
    weights = rng.uniform(0.1, 5.0, n)
    if scenario == "zero_demand":
        floors[seg == 0] = 0.0
        ceils[seg == 0] = 0.0
    elif scenario == "all_reserved":
        ceils = floors.copy()
    elif scenario == "single_vm":
        keep = np.zeros(n, dtype=bool)
        keep[np.unique(seg, return_index=True)[1]] = True
        floors, ceils, weights, seg = (floors[keep], ceils[keep],
                                       weights[keep], seg[keep])
    elif scenario == "empty_host":
        seg = np.where(seg == 1, 2, seg)     # host 1 has no VMs
    total_floor = np.bincount(seg, weights=floors, minlength=n_segs)
    if scenario == "budget_below_floor":
        capacity = total_floor * rng.uniform(0.1, 0.9, n_segs)
    else:
        capacity = rng.uniform(0.0, 3000.0, n_segs)
    return capacity, floors, ceils, weights, seg, n_segs


# ------------------------------------------------------------ core checks
def check_dense_parity(seed: int, scenario: str):
    capacity, floors, ceils, weights, active = dense_problem(seed, scenario)
    with jax.enable_x64(True):
        got = np.asarray(ops.pallas_waterfill_dense(
            capacity, floors, ceils, weights, active=active))
        want = np.asarray(ref.lax_waterfill_dense(
            capacity, floors, ceils, weights, active=active))
    np_res = waterfill_dense_math(np, NUMPY.fori, capacity, floors, ceils,
                                  weights, active=active)
    assert got.dtype == np.float64
    assert np.array_equal(got, want), (
        f"pallas != lax (bitwise), max diff {np.abs(got - want).max()}")
    np.testing.assert_allclose(np_res, want, rtol=1e-9, atol=1e-9)


def check_balance_parity(seed: int, scenario: str):
    hosts, caps0, dense, cpu_res, budget, enabled = balance_problem(
        seed, scenario)
    params = kernels.BalanceParams()
    with jax.enable_x64(True):
        hosts_j = kernels.HostCols(*(jnp.asarray(c) for c in hosts))
        caps_p, did_p, rounds_p = ops.pallas_balance_caps(
            hosts_j, jnp.asarray(caps0), dense, jnp.asarray(cpu_res),
            jnp.asarray(budget), jnp.asarray(enabled), params)
        caps_l, did_l, rounds_l = ref.lax_balance_caps(
            hosts, caps0, dense, cpu_res, budget, enabled, params)
        caps_p, did_p = np.asarray(caps_p), np.asarray(did_p)
        caps_l, did_l = np.asarray(caps_l), np.asarray(did_l)
    np.testing.assert_array_max_ulp(caps_p, caps_l,
                                    maxulp=BALANCE_CAPS_MAXULP)
    assert np.array_equal(did_p, did_l)
    assert int(rounds_p) == int(rounds_l)


def check_segmented_parity(seed: int, scenario: str):
    capacity, floors, ceils, weights, seg, n_segs = segmented_problem(
        seed, scenario)
    got = np.asarray(ops.pallas_waterfill_segmented(
        capacity, floors, ceils, weights, seg, n_segs))
    mirror = np.asarray(ref.lax_waterfill_segmented(
        capacity, floors, ceils, weights, seg, n_segs))
    core = waterfill_core(NUMPY, capacity, floors, ceils,
                          np.maximum(weights, 1e-12), seg, n_segs)
    assert np.array_equal(got, mirror), (
        f"pallas segmented != lax mirror (bitwise), max diff "
        f"{np.abs(got - mirror).max()}")
    np.testing.assert_allclose(got, core, rtol=1e-9, atol=1e-9)


# -------------------------------------------------- seed-parametrized fuzz
@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("seed", SEEDS)
def test_dense_waterfill_parity(seed, scenario):
    check_dense_parity(seed, scenario)


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("seed", SEEDS[:3])
def test_balance_caps_parity(seed, scenario):
    check_balance_parity(seed, scenario)


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("seed", SEEDS)
def test_segmented_waterfill_parity(seed, scenario):
    check_segmented_parity(seed, scenario)


# ------------------------------------------------- hypothesis-driven fuzz
if HAVE_HYPOTHESIS:
    @needs_hypothesis
    @given(seed=st.integers(0, 2**32 - 1),
           scenario=st.sampled_from(SCENARIOS))
    def test_dense_waterfill_parity_hypothesis(seed, scenario):
        check_dense_parity(seed, scenario)

    @needs_hypothesis
    @given(seed=st.integers(0, 2**32 - 1),
           scenario=st.sampled_from(SCENARIOS))
    def test_balance_caps_parity_hypothesis(seed, scenario):
        check_balance_parity(seed, scenario)

    @needs_hypothesis
    @given(seed=st.integers(0, 2**32 - 1),
           scenario=st.sampled_from(SCENARIOS))
    def test_segmented_waterfill_parity_hypothesis(seed, scenario):
        check_segmented_parity(seed, scenario)


# ------------------------------------------------- executor registry/wiring
def test_executor_registry_validates():
    with pytest.raises(ValueError):
        backend_mod.set_executor("cuda")
    with backend_mod.executor_scope("jax-pallas"):
        assert backend_mod.executor_name() == "jax-pallas"
        assert backend_mod.pallas_enabled()
    assert backend_mod.executor_name() == "jax"
    assert not backend_mod.pallas_enabled()


def test_executor_env_validation(monkeypatch):
    monkeypatch.setenv("REPRO_EXECUTOR", "tpu-magic")
    with pytest.raises(ValueError):
        backend_mod.executor_name()
    monkeypatch.setenv("REPRO_EXECUTOR", "jax-pallas")
    assert backend_mod.pallas_enabled()


def test_numpy_entry_lifts_to_segmented_kernel():
    """``batched_waterfill`` (the VectorSimulator delivery primitive)
    reaches the segmented Pallas kernel under the jax-pallas executor and
    matches its NumPy result to reduction-order rounding."""
    capacity, floors, ceils, weights, seg, n_segs = segmented_problem(
        0, "plain")
    want = batched_waterfill(capacity, floors, ceils, weights, seg, n_segs)
    with backend_mod.executor_scope("jax-pallas"):
        got = batched_waterfill(capacity, floors, ceils, weights, seg,
                                n_segs)
    assert isinstance(got, np.ndarray)
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)


def test_dense_dispatcher_routes_to_pallas():
    """``waterfill_dense`` on the JAX plane must give bitwise-equal results
    whether the executor dispatches to Pallas or stays on lax."""
    capacity, floors, ceils, weights, active = dense_problem(1, "plain")
    be = backend_mod.jax_backend()
    with jax.enable_x64(True):
        args = (jnp.asarray(capacity), jnp.asarray(floors),
                jnp.asarray(ceils), jnp.asarray(weights))
        act = jnp.asarray(active)
        with backend_mod.executor_scope("jax"):
            want = np.asarray(waterfill_dense(jnp, be.fori, *args,
                                              active=act))
        with backend_mod.executor_scope("jax-pallas"):
            got = np.asarray(waterfill_dense(jnp, be.fori, *args,
                                             active=act))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("driver", ("dense", "balance", "segmented"))
def test_drivers_refuse_tpu(monkeypatch, driver):
    """On a TPU backend every Pallas driver raises ``PallasUnsupported``
    before it builds a kernel: a float64 ``pallas_call`` does not lower
    there."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ops.PallasUnsupported, match="64-bit"):
        if driver == "dense":
            capacity, floors, ceils, weights, active = dense_problem(
                0, "plain")
            ops.pallas_waterfill_dense(capacity, floors, ceils, weights,
                                       active=active)
        elif driver == "balance":
            hosts, caps0, dense, cpu_res, budget, enabled = balance_problem(
                0, "plain")
            ops.pallas_balance_caps(hosts, caps0, dense, cpu_res, budget,
                                    enabled, kernels.BalanceParams())
        else:
            ops.pallas_waterfill_segmented(*segmented_problem(0, "plain"))


def test_object_plane_balance_under_pallas_executor():
    """``balance_power_cap`` (ManagerCore's phase 2) runs through the fused
    kernel under the jax-pallas executor, with the same protocol outcome as
    the NumPy executor (entitlements differ only by reduction order)."""
    from repro.core.balance import balance_power_cap
    from repro.core.power_model import PAPER_HOST
    from repro.drs.snapshot import ClusterSnapshot, Host, VirtualMachine

    hosts = [Host(f"h{i}", PAPER_HOST, power_cap=250.0) for i in range(3)]
    vms = []
    for i in range(9):
        vms.append(VirtualMachine(
            vm_id=f"vm{i}", host_id=f"h{i % 3}",
            demand=[400.0, 2200.0, 900.0][i % 3],
            reservation=100.0, shares=1000))
    snap = ClusterSnapshot(hosts, vms, power_budget=750.0)
    want, did_want = balance_power_cap(snap)
    with backend_mod.executor_scope("jax-pallas"):
        got, did_got = balance_power_cap(snap)
    assert did_got == did_want
    want_caps = [h.power_cap for h in want.hosts.values()]
    got_caps = [h.power_cap for h in got.hosts.values()]
    np.testing.assert_allclose(got_caps, want_caps, rtol=1e-6, atol=1e-6)


# --------------------------------------------------- padded-slot leak fix
def test_padded_slot_leak_regression():
    """Poisoned padding: stale demand left in inactive slots must not
    absorb entitlement when the ``active`` mask is passed.  (Without the
    mask the poison visibly corrupts the allocation -- that is the leak
    this guards against.)"""
    capacity, floors, ceils, weights, active = dense_problem(3, "plain")
    poison_f = np.where(active, floors, 7e5)
    poison_c = np.where(active, ceils, 9e5)
    poison_w = np.where(active, weights, 50.0)
    clean = waterfill_dense_math(np, NUMPY.fori, capacity, floors, ceils,
                                 np.where(active, weights, 1e-12))

    # The leak exists without the mask: poisoned slots soak up capacity.
    leaked = waterfill_dense_math(np, NUMPY.fori, capacity, poison_f,
                                  poison_c, poison_w)
    assert not np.allclose(np.where(active, leaked, 0.0),
                           np.where(active, clean, 0.0))

    # With the mask, every executor neutralizes the poison bit-for-bit.
    masked_np = waterfill_dense_math(np, NUMPY.fori, capacity, poison_f,
                                     poison_c, poison_w, active=active)
    assert np.array_equal(masked_np, clean)
    with jax.enable_x64(True):
        masked_lax = np.asarray(ref.lax_waterfill_dense(
            capacity, poison_f, poison_c, poison_w, active=active))
        masked_pl = np.asarray(ops.pallas_waterfill_dense(
            capacity, poison_f, poison_c, poison_w, active=active))
    np.testing.assert_allclose(masked_lax, clean, rtol=1e-9, atol=1e-9)
    assert np.array_equal(masked_pl, masked_lax)


def test_inactive_slots_allocate_nothing():
    capacity, floors, ceils, weights, active = dense_problem(4, "plain")
    poison_c = np.where(active, ceils, 9e5)
    out = waterfill_dense_math(np, NUMPY.fori, capacity, floors, poison_c,
                               weights, active=active)
    assert np.all(out[~active] == 0.0)
