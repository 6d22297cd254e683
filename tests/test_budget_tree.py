"""Property suite locking down hierarchical budget trees.

Four pinned properties (plus regressions) over random hierarchies:

  * **Invariant** -- after any manager invocation, every tree node's
    powered-on subtree cap-sum stays within its limit (checked by
    brute-force Python sums, independent of the engines' own asserts).
  * **Flat bit-identity** -- a single-level tree that adds no constraint
    (root at the scalar budget, one unlimited leaf per host) produces
    *bit-identical* actions to the scalar-budget protocol on all three
    engines: object, vector, and batched.
  * **Monotonicity** -- tightening any node's limit never increases any
    host's projected cap (and a live service's ``NodeLimitChange`` never
    raises a cap).
  * **Headroom parity** -- the admission service's ``headroom`` answers
    equal brute-force recomputation from first principles, before and
    after replaying a mixed event feed.

Regressions: power-on funding's donor/pool scope stops at the requester's
tightest binding ancestor (a saturated row cannot be over-funded from
another row's watts), and DPM evacuation scope collapses to the binding
subtree.  Like the kernel-invariant harness, fuzzing runs as an always-on
seed sweep plus hypothesis-driven generation when hypothesis is available.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import kernels
from repro.core.budget_tree import BudgetTree
from repro.core.manager import CloudPowerCapManager, ManagerConfig
from repro.core.power_model import PAPER_HOST
from repro.core.redistribute import redistribute_for_power_on
from repro.drs import balancer as balancer_mod
from repro.drs.snapshot import ClusterSnapshot, Host, VirtualMachine
from repro.runtime.budget_service import (BudgetService, NodeLimitChange,
                                          synthetic_feed)
from repro.sim import workloads
from repro.sim.batch import BatchCell, BatchedSimulator
from repro.sim.cluster import SimConfig, Simulator
from repro.sim.engine import VectorSimulator

try:
    from hypothesis import given, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS,
    reason="hypothesis-driven fuzzing needs hypothesis (requirements.txt)")

SEEDS = tuple(range(5))


# ------------------------------------------------------------- generators
def random_tree(rng, n_hosts, budget):
    """A random feasible hierarchy: parents precede children, hosts hang
    off arbitrary nodes, and every non-root limit grants its subtree at
    least ~idle power per host (so reservation floors always fit) while
    often undercutting the pro-rata share (so limits actually bind)."""
    n_nodes = 1 + rng.randint(0, 4)
    parent = [-1] + [int(rng.randint(0, m)) for m in range(1, n_nodes)]
    host_node = rng.randint(0, n_nodes, size=n_hosts)
    probe = BudgetTree(parent, [budget] * n_nodes, host_node)
    limit = [float(budget)]
    for m in range(1, n_nodes):
        k = max(int(probe.subtree_hosts(m).sum()), 1)
        limit.append(k * float(rng.uniform(185.0, 330.0)))
    return BudgetTree(parent, limit, host_node)


def random_cluster(rng, tree, budget, n_hosts):
    hosts = [Host(f"h{i}", PAPER_HOST,
                  power_cap=float(rng.uniform(170.0, 320.0)),
                  powered_on=bool(rng.rand() > 0.15))
             for i in range(n_hosts)]
    if not any(h.powered_on for h in hosts):
        hosts[0].powered_on = True
    vms = []
    for i in range(2 * n_hosts):
        owner = hosts[i % n_hosts]
        if not owner.powered_on:
            continue
        vms.append(VirtualMachine(
            vm_id=f"vm{i}", vcpus=2, memory_mb=4096.0,
            demand=float(rng.uniform(0.0, 6000.0)),
            mem_demand=float(rng.uniform(256.0, 2048.0)),
            host_id=owner.host_id))
    return ClusterSnapshot(hosts, vms, power_budget=budget, budget_tree=tree)


def _cap_only_manager() -> CloudPowerCapManager:
    cfg = ManagerConfig(powercap_enabled=True, dpm_enabled=False)
    cfg.balancer = balancer_mod.BalancerConfig(max_moves=0)
    return CloudPowerCapManager(cfg)


def brute_force_overshoot(tree, caps, on):
    """Worst per-node limit violation, recomputed with Python sums."""
    worst = -np.inf
    for m in range(tree.n_nodes):
        members = np.nonzero(tree.subtree_hosts(m))[0]
        used = sum(float(caps[j]) for j in members if on[j])
        worst = max(worst, used - float(tree.limit[m]))
    return worst


# --------------------------------------------- property 1: tree invariant
def check_manager_tree_invariant(seed):
    rng = np.random.RandomState(seed)
    n_hosts = int(rng.randint(3, 7))
    budget = 300.0 * n_hosts
    tree = random_tree(rng, n_hosts, budget)
    snap = random_cluster(rng, tree, budget, n_hosts)
    res = _cap_only_manager().run_invocation(snap)
    final = list(res.snapshot.hosts.values())
    caps = np.array([h.power_cap for h in final])
    on = np.array([h.powered_on for h in final])
    assert brute_force_overshoot(tree, caps, on) <= 1e-6
    assert caps[on].sum() <= budget + 1e-6


@pytest.mark.parametrize("seed", SEEDS)
def test_manager_respects_tree_invariant(seed):
    check_manager_tree_invariant(seed)


# ------------------------------------------ property 2: flat bit-identity
def star_flat_tree(budget, n_hosts):
    """An ``n_hosts + 1``-node tree that adds no constraint: root at the
    scalar budget, one unlimited leaf per host.  Non-trivial (so the tree
    code path actually runs in every engine) but non-binding, so the
    protocol must behave bit-identically to the scalar budget."""
    parent = [-1] + [0] * n_hosts
    limit = [float(budget)] + [np.inf] * n_hosts
    return BudgetTree(parent, limit, np.arange(1, n_hosts + 1))


def _burst_build(tree_builder):
    hosts = [Host(f"h{i}", PAPER_HOST, power_cap=250.0) for i in range(4)]
    vms, traces = [], {}
    for i in range(8):
        vm = VirtualMachine(vm_id=f"vm{i}", vcpus=2, memory_mb=4096.0,
                            host_id=f"h{i % 4}")
        vms.append(vm)
        if i % 4 == 0:        # hosts 0's VMs burst at 400 s -> cap churn
            traces[vm.vm_id] = workloads.step_trace(
                [(0.0, 800.0, 1024.0), (400.0, 6000.0, 1024.0)])
        else:
            traces[vm.vm_id] = workloads.step_trace([(0.0, 800.0, 1024.0)])
    budget = 4 * 250.0
    tree = tree_builder(budget, 4) if tree_builder else None
    snap = ClusterSnapshot(hosts, vms, power_budget=budget, budget_tree=tree)
    cfg = SimConfig(duration_s=900.0, drs_first_at_s=300.0,
                    record_timeline=False)
    return snap, traces, cfg


def _run_burst(engine, tree_builder):
    """(accumulators, final caps) for the burst scenario on one engine."""
    snap, traces, cfg = _burst_build(tree_builder)
    if engine == "batch":
        cell = BatchCell("cell", snap, traces, cfg, powercap_enabled=True)
        res = BatchedSimulator([cell]).run()
        return res.accumulators(0), np.asarray(res.final_caps[0])
    cls = Simulator if engine == "legacy" else VectorSimulator
    res = cls(snap, _cap_only_manager(), traces, cfg).run()
    caps = np.array([h.power_cap for h in res.final.hosts.values()])
    return res.acc, caps


@pytest.mark.parametrize("engine", ("legacy", "vector", "batch"))
def test_flat_tree_bit_identical_to_scalar(engine):
    acc0, caps0 = _run_burst(engine, None)
    acc1, caps1 = _run_burst(engine, star_flat_tree)
    assert acc0.cap_changes > 0          # the scenario exercises the caps
    for f in ("cap_changes", "vmotions", "power_ons", "power_offs",
              "cpu_payload_mhz_s", "mem_payload_mb_s", "energy_j"):
        assert getattr(acc1, f) == getattr(acc0, f), f
    np.testing.assert_array_equal(caps1, caps0)


def test_trivial_flat_tree_skips_tree_path():
    """``BudgetTree.flat`` encodes exactly the scalar budget; engines skip
    the tree code entirely for it."""
    snap, _, _ = _burst_build(lambda b, h: BudgetTree.flat(b, h))
    assert snap.budget_tree is not None
    assert snap.effective_tree() is None


# --------------------------------------------- property 3: monotonicity
def check_tightening_monotone(seed):
    rng = np.random.RandomState(seed)
    n_hosts = int(rng.randint(3, 9))
    budget = 300.0 * n_hosts
    tree = random_tree(rng, n_hosts, budget)
    caps = rng.uniform(0.0, 320.0, n_hosts)
    floors = caps * rng.uniform(0.0, 0.6, n_hosts)
    on = rng.rand(n_hosts) > 0.2
    base = tree.project(caps, on, floors=floors)
    # Projection sanity: never above the input, never below the floors.
    assert np.all(base[on] <= caps[on] + 1e-9)
    assert np.all(base[on] >= floors[on] - 1e-9)
    # Tightening any single node's limit never increases any host's cap.
    node = int(rng.randint(0, tree.n_nodes))
    lam = float(rng.uniform(0.3, 1.0))
    tight = tree.with_limit(node, float(tree.limit[node]) * lam)
    assert np.all(tight.project(caps, on, floors=floors)
                  <= base + 1e-9)


@pytest.mark.parametrize("seed", SEEDS)
def test_tightening_never_raises_caps(seed):
    check_tightening_monotone(seed)


def check_service_limit_change_monotone(seed):
    rng = np.random.RandomState(seed)
    n_hosts = int(rng.randint(3, 9))
    budget = 300.0 * n_hosts
    tree = random_tree(rng, n_hosts, budget)
    on = rng.rand(n_hosts) > 0.25
    caps = tree.project(rng.uniform(100.0, 300.0, n_hosts), on)
    svc = BudgetService(tree, [f"host{i}" for i in range(n_hosts)], caps, on)
    before = svc.caps.copy()
    node = int(rng.randint(0, tree.n_nodes))
    new_limit = float(tree.limit[node]) * float(rng.uniform(0.3, 1.0))
    if not np.isfinite(new_limit):
        new_limit = budget * 0.5
    _, decisions = svc.handle(NodeLimitChange(node, new_limit))
    assert np.all(svc.caps[svc.on] <= before[svc.on] + 1e-9)
    for d in decisions:                  # streamed decisions only decrease
        assert d.cap_w <= before[svc._host(d.host_id)] + 1e-9


@pytest.mark.parametrize("seed", SEEDS)
def test_service_limit_change_never_raises_caps(seed):
    check_service_limit_change_monotone(seed)


# ------------------------------------------ property 4: headroom parity
def check_service_headroom_brute_force(seed):
    rng = np.random.RandomState(seed)
    n_hosts = int(rng.randint(3, 9))
    budget = 300.0 * n_hosts
    tree = random_tree(rng, n_hosts, budget)
    on = rng.rand(n_hosts) > 0.25
    caps = tree.project(rng.uniform(100.0, 300.0, n_hosts), on)
    ids = [f"host{i}" for i in range(n_hosts)]
    svc = BudgetService(tree, ids, caps, on)
    for h in ids:
        assert svc.headroom(h) == pytest.approx(
            svc.brute_force_headroom(h), abs=1e-9)
    # Still in lockstep after churning through a mixed event feed.
    svc.replay(synthetic_feed(tree, n_events=300, seed=seed))
    for h in ids:
        assert svc.headroom(h) == pytest.approx(
            svc.brute_force_headroom(h), abs=1e-9)


@pytest.mark.parametrize("seed", SEEDS)
def test_service_headroom_matches_brute_force(seed):
    check_service_headroom_brute_force(seed)


# ------------------------------------------------- hypothesis-driven fuzz
if HAVE_HYPOTHESIS:
    @needs_hypothesis
    @given(seed=st.integers(0, 2**32 - 1))
    def test_manager_tree_invariant_hypothesis(seed):
        check_manager_tree_invariant(seed)

    @needs_hypothesis
    @given(seed=st.integers(0, 2**32 - 1))
    def test_tightening_monotone_hypothesis(seed):
        check_tightening_monotone(seed)

    @needs_hypothesis
    @given(seed=st.integers(0, 2**32 - 1))
    def test_service_headroom_hypothesis(seed):
        check_service_headroom_brute_force(seed)

    @needs_hypothesis
    @given(seed=st.integers(0, 2**32 - 1))
    def test_service_limit_change_monotone_hypothesis(seed):
        check_service_limit_change_monotone(seed)


# ------------------------------------------------------------ regressions
def test_power_on_funding_respects_binding_row():
    """Satellite fix: the funding pool and donor set stop at the
    requester's tightest binding ancestor.  Row 1 (limit 400 W) holds one
    busy host at 320 W; funding its standby neighbor may grant at most the
    row's 80 W of headroom even though the rack has 280 W unallocated --
    the scalar protocol (no tree) would grant far more and blow the row
    limit by ~200 W."""
    budget = 1100.0
    tree = BudgetTree.two_rows(budget, 4, row0_limit=700.0,
                               row1_limit=400.0)

    def build(with_tree):
        hosts = [Host("h0", PAPER_HOST, power_cap=250.0),
                 Host("h1", PAPER_HOST, power_cap=250.0),
                 Host("h2", PAPER_HOST, power_cap=320.0),
                 Host("h3", PAPER_HOST, power_cap=160.0, powered_on=False)]
        vms = [VirtualMachine(vm_id="busy0", vcpus=8, memory_mb=8192.0,
                              demand=33000.0, host_id="h2"),
               VirtualMachine(vm_id="idle0", vcpus=1, memory_mb=2048.0,
                              demand=500.0, host_id="h0"),
               VirtualMachine(vm_id="idle1", vcpus=1, memory_mb=2048.0,
                              demand=500.0, host_id="h1")]
        return ClusterSnapshot(hosts, vms, power_budget=budget,
                               budget_tree=tree if with_tree else None)

    whatif, granted = redistribute_for_power_on(build(True), "h3")
    assert granted == pytest.approx(80.0, abs=1e-6)
    # Donors outside the binding row are untouched.
    assert whatif.hosts["h0"].power_cap == 250.0
    assert whatif.hosts["h1"].power_cap == 250.0
    # The row limit holds with the pending grant counted as allocated.
    caps = np.array([whatif.hosts[f"h{i}"].power_cap for i in range(4)])
    on_or_pending = np.array([True, True, True, True])
    assert brute_force_overshoot(tree, caps, on_or_pending) <= 1e-6

    # Control: without the tree the same request drains the rack pool.
    _, flat_granted = redistribute_for_power_on(build(False), "h3")
    assert flat_granted >= 250.0


def test_evac_scope_collapses_to_binding_row():
    """Evacuating a host under a saturated row keeps the freed watts and
    displaced demand inside that row; with slack everywhere the scope is
    the whole cluster (the scalar-protocol behavior)."""
    tree = BudgetTree.two_rows(1000.0, 4, row0_limit=500.0)
    tc = tree.cols()
    on = np.ones((1, 4), dtype=bool)
    victim = np.array([0])
    saturated = np.array([[250.0, 250.0, 100.0, 100.0]])
    scope = kernels.tree_evac_scope(np, tc, on, saturated, victim)
    np.testing.assert_array_equal(scope,
                                  [[True, True, False, False]])
    relaxed = np.array([[200.0, 250.0, 100.0, 100.0]])
    scope = kernels.tree_evac_scope(np, tc, on, relaxed, victim)
    np.testing.assert_array_equal(scope, [[True, True, True, True]])


@pytest.mark.parametrize("seed", SEEDS[:3])
def test_tree_kernels_numpy_jax_parity(seed):
    """The segment ops behind every tree decision agree across executors
    (the batched engine must pick the same actions as the NumPy planes)."""
    rng = np.random.RandomState(seed)
    n_hosts = int(rng.randint(3, 9))
    budget = 300.0 * n_hosts
    tree = random_tree(rng, n_hosts, budget)
    tc = tree.cols()
    on = (rng.rand(1, n_hosts) > 0.2)
    caps = rng.uniform(0.0, 320.0, (1, n_hosts))
    floors = caps * rng.uniform(0.0, 0.6, (1, n_hosts))
    victim = np.array([int(rng.randint(0, n_hosts))])

    ref_sums = kernels.tree_node_sums(np, tc, on, caps)
    ref_slack = kernels.tree_host_slack(
        np, tc, kernels.tree_headroom(np, tc, on, caps))
    ref_proj = kernels.tree_project_caps(np, tc, on, caps, floors)
    ref_scope = kernels.tree_evac_scope(np, tc, on, caps, victim)

    with jax.enable_x64(True):
        tcj = kernels.TreeCols(jnp.asarray(tc.anc), jnp.asarray(tc.limit),
                               jnp.asarray(tc.depth))
        onj, capsj = jnp.asarray(on), jnp.asarray(caps)
        got_sums = np.asarray(kernels.tree_node_sums(jnp, tcj, onj, capsj))
        got_slack = np.asarray(kernels.tree_host_slack(
            jnp, tcj, kernels.tree_headroom(jnp, tcj, onj, capsj)))
        got_proj = np.asarray(kernels.tree_project_caps(
            jnp, tcj, onj, capsj, jnp.asarray(floors)))
        got_scope = np.asarray(kernels.tree_evac_scope(
            jnp, tcj, onj, capsj, jnp.asarray(victim)))

    np.testing.assert_allclose(got_sums, ref_sums, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(got_slack, ref_slack, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(got_proj, ref_proj, rtol=1e-12, atol=1e-12)
    np.testing.assert_array_equal(got_scope, ref_scope)


# ------------------------------------------------------- constructor edges
def test_tree_constructor_validation():
    with pytest.raises(ValueError, match="at least a root"):
        BudgetTree([], [], [])
    with pytest.raises(ValueError, match="root"):
        BudgetTree([0, -1], [100.0, 100.0], [1])
    with pytest.raises(ValueError, match="precede"):
        BudgetTree([-1, 2, 1], [100.0] * 3, [0])
    with pytest.raises(ValueError, match="non-negative"):
        BudgetTree([-1], [-5.0], [0])
    with pytest.raises(ValueError, match="unknown node"):
        BudgetTree([-1, 0], [100.0, 50.0], [2])
    with pytest.raises(ValueError, match="length mismatch"):
        BudgetTree([-1, 0], [100.0], [0])


def test_with_limit_is_copy_on_write():
    tree = BudgetTree.two_rows(1000.0, 4, row0_limit=400.0)
    tight = tree.with_limit(1, 300.0)
    assert tree.limit[1] == 400.0 and tight.limit[1] == 300.0
    assert tight.parent is not tree.limit
    np.testing.assert_array_equal(tight.host_node, tree.host_node)
