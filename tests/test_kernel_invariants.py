"""Property-based invariants of the hot allocation kernels, per executor.

Where ``test_kernel_parity`` pins the executors to each *other*, this file
pins them to the *math*: every invariant below must hold on the ``numpy``,
``jax``, and ``jax-pallas`` executors alike, exercised through the
production dispatchers (``waterfill_dense`` / ``balance_caps``) under
``executor_scope`` so each run takes the same code path the simulator
takes.

Waterfill (weighted max-min):
  * allocations never drop below reserved floors (outside the degenerate
    floors-exceed-capacity regime, where floors are granted pro-rata),
  * never exceed ceilings, and inactive slots allocate exactly nothing,
  * per-host totals never exceed host capacity,
  * totals are monotone in capacity (more budget never shrinks anyone).

BalancePowerCap -- on *any* specs:
  * the cap-spread (population stddev of normalized entitlements over
    powered-on hosts) never increases -- the loop's ``worse`` guard reverts
    any non-improving round,
  * ``did == False`` cells pass through bit-identical.

BalancePowerCap -- on *homogeneous* host specs (identical power/capacity
maps within a cell, the paper's cluster setting; heterogeneous maps make
Watts conservation approximate by design -- the kernel's over-budget trim
is documented as a safety net, not an exact bound):
  * hosts that shrank keep ``managed >= cpu_reserved`` (their VMs'
    reservations stay admissible),
  * the powered-on cap total never grows past the cluster budget -- or,
    when the budget starts out violated (``budget_below_floor``), past the
    total it started with.

Like the parity harness, fuzzing runs as an always-on seed sweep plus
hypothesis-driven generation when hypothesis is available.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import backend as backend_mod
from repro.backend import NUMPY
from repro.core import kernels
from repro.drs.entitlement import waterfill_dense, waterfill_dense_math

from test_kernel_parity import SCENARIOS, balance_problem, dense_problem

try:
    from hypothesis import given, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS,
    reason="hypothesis-driven fuzzing needs hypothesis (requirements.txt)")

EXECUTORS = ("numpy", "jax", "jax-pallas")
SEEDS = tuple(range(4))


# ------------------------------------------------------- executor runners
def run_waterfill(executor, capacity, floors, ceils, weights, active):
    """The production ``waterfill_dense`` dispatcher on the named executor,
    result on the NumPy plane."""
    if executor == "numpy":
        with backend_mod.executor_scope(executor):
            return waterfill_dense(np, NUMPY.fori, capacity, floors, ceils,
                                   weights, active=active)
    be = backend_mod.jax_backend()
    with jax.enable_x64(True), backend_mod.executor_scope(executor):
        out = waterfill_dense(jnp, be.fori, jnp.asarray(capacity),
                              jnp.asarray(floors), jnp.asarray(ceils),
                              jnp.asarray(weights),
                              active=jnp.asarray(active))
        return np.asarray(out)


def run_balance(executor, problem):
    """The production ``balance_caps`` driver on the named executor, with
    the dense-slot ``ents_at`` that executor would use in the simulator."""
    hosts, caps0, dense, cpu_res, budget, enabled = problem
    params = kernels.BalanceParams()
    if executor == "numpy":
        def ents_at(c):
            managed = kernels.managed_capacity(np, hosts, c)
            alloc = waterfill_dense(np, NUMPY.fori, managed, dense.floors,
                                    dense.ceils, dense.weights,
                                    active=dense.active)
            return np.sum(alloc, axis=-1)

        with backend_mod.executor_scope(executor):
            caps, did, _ = kernels.balance_caps(
                NUMPY, hosts, caps0.copy(), ents_at, cpu_res, budget,
                enabled, params)
        return np.asarray(caps), np.asarray(did)
    be = backend_mod.jax_backend()
    with jax.enable_x64(True), backend_mod.executor_scope(executor):
        hosts_j = kernels.HostCols(*(jnp.asarray(c) for c in hosts))
        dense_j = kernels.DenseCols(
            jnp.asarray(dense.floors), jnp.asarray(dense.ceils),
            jnp.asarray(dense.weights), jnp.asarray(dense.active))

        def ents_at(c):
            managed = kernels.managed_capacity(jnp, hosts_j, c)
            alloc = waterfill_dense(jnp, be.fori, managed, dense_j.floors,
                                    dense_j.ceils, dense_j.weights,
                                    active=dense_j.active)
            return jnp.sum(alloc, axis=-1)

        caps, did, _ = kernels.balance_caps(
            be, hosts_j, jnp.asarray(caps0), ents_at, jnp.asarray(cpu_res),
            jnp.asarray(budget), jnp.asarray(enabled), params,
            dense=dense_j)
        return np.asarray(caps), np.asarray(did)


def homogeneous_balance_problem(seed, scenario, s=2, h=5, j=6):
    """``balance_problem`` with per-cell *uniform* host specs, so the
    Watts<->capacity maps are identical within a cell and transfers conserve
    Watts exactly (the regime where the reserved-floor and budget bounds
    are exact kernel guarantees, not safety nets)."""
    hosts, _, dense, _, _, enabled = balance_problem(seed, scenario, s, h, j)

    def col(a):
        return np.broadcast_to(np.asarray(a)[..., :1], (s, h)).copy()

    hosts = kernels.HostCols(hosts.on, col(hosts.power_idle),
                             col(hosts.power_peak),
                             col(hosts.capacity_peak),
                             col(hosts.hyp_overhead))
    rng = np.random.default_rng(seed ^ 0x40)
    caps0 = rng.uniform(hosts.power_idle, hosts.power_peak)
    managed0 = kernels.managed_capacity(np, hosts, caps0)
    cpu_res = managed0 * rng.uniform(0.0, 0.8, (s, h))
    budget = np.sum(np.where(hosts.on, caps0, 0.0), axis=-1)
    if scenario == "budget_below_floor":
        budget = budget * 0.5
    return hosts, caps0, dense, cpu_res, budget, enabled


def _spread(hosts, caps, dense):
    """Cap-spread on the NumPy plane: masked stddev of normalized
    entitlements over powered-on hosts (what the loop's ``worse`` guard
    measures, recomputed in float64)."""
    managed = kernels.managed_capacity(np, hosts, caps)
    alloc = waterfill_dense_math(np, NUMPY.fori, managed, dense.floors,
                                 dense.ceils, dense.weights,
                                 active=dense.active)
    ents = np.sum(alloc, axis=-1)
    ns = np.where(managed > 0.0, ents / np.maximum(managed, 1e-300), 0.0)
    n_on = np.sum(hosts.on, axis=-1)
    return kernels._masked_std(np, ns, hosts.on, n_on)


# ------------------------------------------------------------ core checks
def check_waterfill_invariants(executor, seed, scenario):
    capacity, floors, ceils, weights, active = dense_problem(seed, scenario)
    out = run_waterfill(executor, capacity, floors, ceils, weights, active)
    assert out.shape == floors.shape

    # Inactive slots allocate exactly nothing; nothing is ever negative.
    assert np.all(out[~active] == 0.0)
    assert np.all(out >= 0.0)

    # Floors honored wherever the capacity can cover them; the degenerate
    # regime grants floors pro-rata (so allocations sit *below* floors).
    total_floor = floors.sum(axis=-1)
    degenerate = total_floor >= capacity
    assert np.all(out[~degenerate] >= floors[~degenerate] - 1e-9)
    assert np.all(out[degenerate] <= floors[degenerate] + 1e-9)

    # Ceilings (lifted to floors) honored everywhere.
    assert np.all(out <= np.maximum(ceils, floors) + 1e-9)

    # Per-host totals never exceed the host's capacity.
    sums = out.sum(axis=-1)
    assert np.all(sums <= capacity + 1e-6)

    # Monotone in capacity: more budget never shrinks a host's total.
    bigger = capacity * 1.25 + 1.0
    sums2 = run_waterfill(executor, bigger, floors, ceils, weights,
                          active).sum(axis=-1)
    assert np.all(sums2 >= sums - 1e-6)


def check_balance_robust_invariants(executor, seed, scenario):
    """Invariants that hold on arbitrary (heterogeneous) host specs."""
    problem = balance_problem(seed, scenario)
    hosts, caps0, dense, cpu_res, budget, enabled = problem
    caps, did = run_balance(executor, problem)
    assert caps.shape == caps0.shape and did.shape == enabled.shape

    # Cells that did nothing pass through bit-identical.
    for s in range(caps.shape[0]):
        if not did[s]:
            assert np.array_equal(caps[s], caps0[s])

    # The cap-spread never increases: the loop's ``worse`` guard reverts
    # any round that would widen it.
    assert np.all(_spread(hosts, caps, dense)
                  <= _spread(hosts, caps0, dense) + 1e-7)


def check_balance_exact_invariants(executor, seed, scenario):
    """Watts-conservation invariants, exact on homogeneous host specs."""
    problem = homogeneous_balance_problem(seed, scenario)
    hosts, caps0, dense, cpu_res, budget, enabled = problem
    caps, did = run_balance(executor, problem)
    on = hosts.on

    # Spread still never increases, same as the heterogeneous case.
    assert np.all(_spread(hosts, caps, dense)
                  <= _spread(hosts, caps0, dense) + 1e-7)

    total0 = np.sum(np.where(on, caps0, 0.0), axis=-1)
    total = np.sum(np.where(on, caps, 0.0), axis=-1)
    if scenario == "budget_below_floor":
        # Budget starts out violated: transfers conserve and the over-budget
        # trim only takes, so the total never grows past where it started.
        assert np.all(total <= total0 + 1e-6 * (1.0 + total0))
        return

    # Conserving transfers keep the powered-on total within the budget.
    assert np.all(total <= budget + 1e-6 * (1.0 + budget))

    # Shrunk hosts are donors, and donors never drop below their VMs'
    # reservations: managed capacity stays >= cpu_reserved.
    managed = kernels.managed_capacity(np, hosts, caps)
    shrunk = on & (caps < caps0 - 1e-6)
    assert np.all(~shrunk | (managed >= cpu_res - 1e-6))


# -------------------------------------------------- seed-parametrized fuzz
@pytest.mark.parametrize("executor", EXECUTORS)
@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("seed", SEEDS)
def test_waterfill_invariants(executor, seed, scenario):
    check_waterfill_invariants(executor, seed, scenario)


@pytest.mark.parametrize("executor", EXECUTORS)
@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("seed", SEEDS[:2])
def test_balance_robust_invariants(executor, seed, scenario):
    check_balance_robust_invariants(executor, seed, scenario)


@pytest.mark.parametrize("executor", EXECUTORS)
@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("seed", SEEDS[:2])
def test_balance_exact_invariants(executor, seed, scenario):
    check_balance_exact_invariants(executor, seed, scenario)


# ------------------------------------------------- hypothesis-driven fuzz
if HAVE_HYPOTHESIS:
    @needs_hypothesis
    @given(seed=st.integers(0, 2**32 - 1),
           scenario=st.sampled_from(SCENARIOS),
           executor=st.sampled_from(EXECUTORS))
    def test_waterfill_invariants_hypothesis(seed, scenario, executor):
        check_waterfill_invariants(executor, seed, scenario)

    @needs_hypothesis
    @given(seed=st.integers(0, 2**32 - 1),
           scenario=st.sampled_from(SCENARIOS),
           executor=st.sampled_from(EXECUTORS))
    def test_balance_robust_invariants_hypothesis(seed, scenario, executor):
        check_balance_robust_invariants(executor, seed, scenario)

    @needs_hypothesis
    @given(seed=st.integers(0, 2**32 - 1),
           scenario=st.sampled_from(SCENARIOS),
           executor=st.sampled_from(EXECUTORS))
    def test_balance_exact_invariants_hypothesis(seed, scenario, executor):
        check_balance_exact_invariants(executor, seed, scenario)
