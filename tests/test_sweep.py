"""Scenario-sweep harness: grid generation, deployment math, smoke runs."""

import numpy as np
import pytest

from repro.core.power_model import PAPER_HOST
from repro.sim.sweep import (SMALL_HOST, TWO_ROW_LIMIT_FRAC, SweepSpec,
                             build_sweep, row_contention_specs, run_cell,
                             run_sweep, run_sweep_batched, scale_ladder,
                             scenario_families)


def test_scenario_families_grid():
    specs = scenario_families(sizes=(4, 8), budgets_per_host_w=(250.0,),
                             spikes=("burst", "prime"),
                             heterogeneous=(False, True))
    assert len(specs) == 2 * 1 * 2 * 2
    names = {s.name for s in specs}
    assert len(names) == len(specs)          # unique cell names
    assert any(s.heterogeneous for s in specs)


def test_build_sweep_static_deployment():
    spec = SweepSpec(name="t", n_hosts=6, vms_per_host=4, spike="flat")
    snap, traces, cfg = build_sweep(spec, "static")
    assert len(snap.hosts) == 6
    assert len(snap.vms) == 24
    assert len(traces) == 24
    assert snap.budget_respected()
    # Budget spread evenly across homogeneous hosts.
    caps = {h.power_cap for h in snap.hosts.values()}
    assert len(caps) == 1
    assert cfg.record_timeline is False


def test_build_sweep_statichigh_standby_hosts():
    spec = SweepSpec(name="t", n_hosts=8, spike="flat")  # 2000 W budget
    snap, _, _ = build_sweep(spec, "statichigh")
    on = snap.powered_on_hosts()
    # 2000 W / 320 W peak -> 6 hosts at peak, 2 in standby.
    assert len(on) == 6
    assert all(h.power_cap == PAPER_HOST.power_peak for h in on)
    assert snap.budget_respected()
    # All VMs land on powered-on hosts.
    assert all(snap.vms[v].host_id in {h.host_id for h in on}
               for v in snap.vms)


def test_build_sweep_heterogeneous_mixes_specs():
    spec = SweepSpec(name="t", n_hosts=4, heterogeneous=True, spike="flat")
    snap, _, _ = build_sweep(spec, "cpc")
    specs = {h.spec for h in snap.hosts.values()}
    assert specs == {PAPER_HOST, SMALL_HOST}
    assert snap.budget_respected()


def test_build_sweep_deterministic_by_seed():
    spec = SweepSpec(name="t", n_hosts=4, spike="burst", seed=7)
    a, ta, _ = build_sweep(spec, "cpc")
    b, tb, _ = build_sweep(spec, "cpc")
    assert [v.vm_id for v in a.vms.values()] == \
        [v.vm_id for v in b.vms.values()]
    for vid in ta:
        assert ta[vid](100.0) == tb[vid](100.0)
        assert ta[vid](500.0) == tb[vid](500.0)


def test_unknown_spike_rejected():
    with pytest.raises(ValueError):
        build_sweep(SweepSpec(name="t", spike="nope"), "cpc")


@pytest.mark.parametrize("spike", ("flat", "burst", "step", "prime"))
def test_run_cell_smoke(spike):
    spec = SweepSpec(name=f"s_{spike}", n_hosts=6, vms_per_host=4,
                     spike=spike, duration_s=600.0, tick_s=30.0,
                     drs_period_s=300.0)
    r = run_cell(spec, "cpc")
    assert r.ticks == 20
    assert r.ticks_per_s > 0
    assert 0.0 < r.cpu_satisfaction <= 1.0 + 1e-9
    assert r.energy_j > 0.0
    assert r.vmotions == 0               # migration search disabled in sweeps


def test_sweep_policies_separate_under_burst():
    """Host-correlated bursts strand static caps; CPC recovers the payload."""
    spec = SweepSpec(name="sep", n_hosts=12, vms_per_host=8, spike="burst",
                     duration_s=1200.0, tick_s=20.0, seed=3)
    res = run_sweep([spec], policies=("cpc", "static"))
    cpc, static = res["sep"]["cpc"], res["sep"]["static"]
    assert cpc.cap_changes > 0
    assert static.cap_changes == 0
    assert cpc.cpu_satisfaction >= static.cpu_satisfaction - 1e-9
    assert cpc.cpu_payload_mhz_s >= static.cpu_payload_mhz_s - 1e-6


def test_scale_ladder_shapes():
    ladder = scale_ladder(sizes=(10, 100), spike="burst")
    assert [s.n_hosts for s in ladder] == [10, 100]
    assert all(s.n_vms == 10 * s.n_hosts for s in ladder)


def test_run_sweep_batched_matches_sequential():
    """The jitted grid engine reproduces the sequential sweep cell by cell."""
    specs = scenario_families(sizes=(4,), budgets_per_host_w=(250.0,),
                              spikes=("burst", "prime"),
                              heterogeneous=(False, True),
                              duration_s=600.0, tick_s=30.0)
    policies = ("cpc", "static")
    seq = run_sweep(specs, policies=policies, engine="vector")
    bat = run_sweep(specs, policies=policies, engine="batch")
    assert set(bat) == set(seq)
    for name in seq:
        for p in policies:
            a, b = seq[name][p], bat[name][p]
            assert b.cap_changes == a.cap_changes, (name, p)
            assert b.vmotions == 0
            assert b.ticks == a.ticks
            np.testing.assert_allclose(b.cpu_payload_mhz_s,
                                       a.cpu_payload_mhz_s, rtol=1e-9)
            np.testing.assert_allclose(b.energy_j, a.energy_j, rtol=1e-9)
            np.testing.assert_allclose(b.cpu_satisfaction,
                                       a.cpu_satisfaction, rtol=1e-9)


def test_run_sweep_batched_matches_sequential_churn():
    """Capacity-churn cells (DPM lifecycle, scripted events) reproduce the
    sequential sweep exactly, including the power action counts."""
    specs = scenario_families(sizes=(6,), budgets_per_host_w=(250.0,),
                              spikes=("burst",), heterogeneous=(False,),
                              churns=("none", "dpm", "maintenance",
                                      "failure"),
                              duration_s=1500.0, tick_s=30.0)
    policies = ("cpc", "static")
    seq = run_sweep(specs, policies=policies, engine="vector")
    bat = run_sweep(specs, policies=policies, engine="batch")
    churned = False
    for name in seq:
        for p in policies:
            a, b = seq[name][p], bat[name][p]
            assert (b.cap_changes, b.vmotions, b.power_ons, b.power_offs) \
                == (a.cap_changes, a.vmotions, a.power_ons,
                    a.power_offs), (name, p)
            np.testing.assert_allclose(b.cpu_payload_mhz_s,
                                       a.cpu_payload_mhz_s, rtol=1e-9)
            np.testing.assert_allclose(b.energy_j, a.energy_j, rtol=1e-9)
            churned |= a.power_ons + a.power_offs > 0
    assert churned                       # the grid exercised the lifecycle


def test_run_sweep_batched_matches_sequential_rules():
    """Rule-family cells (constraint corrections, fundable-capacity fits,
    hill-climb balancing) reproduce the sequential sweep exactly."""
    specs = scenario_families(sizes=(8,), budgets_per_host_w=(250.0,),
                              spikes=("burst",), heterogeneous=(False,),
                              rules=("violation_burst", "cap_blocked"),
                              duration_s=600.0, tick_s=10.0)
    policies = ("cpc", "static")
    seq = run_sweep(specs, policies=policies, engine="vector")
    bat = run_sweep(specs, policies=policies, engine="batch")
    migrated = False
    for name in seq:
        for p in policies:
            a, b = seq[name][p], bat[name][p]
            assert (b.cap_changes, b.vmotions) \
                == (a.cap_changes, a.vmotions), (name, p)
            np.testing.assert_allclose(b.cpu_payload_mhz_s,
                                       a.cpu_payload_mhz_s, rtol=1e-9)
            np.testing.assert_allclose(b.energy_j, a.energy_j, rtol=1e-9)
            migrated |= a.vmotions > 0
    assert migrated                 # the grid exercised the migration layer


def test_run_sweep_batched_matches_sequential_timed():
    """Timed-migration families (gated vMotions with copy windows, slot
    limits, and a cluster bandwidth budget) run batched with zero fallback
    cells and reproduce the sequential sweep's action counts exactly and
    its energy to a few ULPs: the arithmetic is the same, but XLA's CPU
    backend (JAX 0.9.0) contracts the Eq. 1 power and the per-tick
    ``acc + tick * dt`` into fused multiply-adds, which round once where
    NumPy rounds twice (1 ULP apart in 4 of these 8 cells).  Payload
    accumulates per-VM delivery in a different reduction order than the
    object plane's bincount, so it is compared at tight tolerance rather
    than exactly."""
    from repro.sim.batch import BatchedSimulator
    from repro.sim.sweep import _build_batch_cells, _grid_balancer

    specs = scenario_families(sizes=(6,), budgets_per_host_w=(250.0,),
                              spikes=("burst",), heterogeneous=(False,),
                              churns=("timed_churn", "failure_cascade"),
                              rules=("none", "violation_burst"),
                              duration_s=1200.0, tick_s=10.0)
    policies = ("cpc", "static")
    cells, _ = _build_batch_cells(specs, policies)
    assert BatchedSimulator.unsupported_cells(
        cells, _grid_balancer(specs)) == {}     # no vector-fallback cliff
    seq = run_sweep(specs, policies=policies, engine="vector")
    bat = run_sweep(specs, policies=policies, engine="batch")
    migrated = churned = False
    for name in seq:
        for p in policies:
            a, b = seq[name][p], bat[name][p]
            assert (b.cap_changes, b.vmotions, b.power_ons, b.power_offs) \
                == (a.cap_changes, a.vmotions, a.power_ons,
                    a.power_offs), (name, p)
            np.testing.assert_array_max_ulp(b.energy_j, a.energy_j,
                                            maxulp=4)
            np.testing.assert_allclose(b.cpu_payload_mhz_s,
                                       a.cpu_payload_mhz_s, rtol=1e-9)
            migrated |= a.vmotions > 0
            churned |= a.power_ons + a.power_offs > 0
    assert migrated                # timed launches committed via the table
    assert churned                 # and the DPM lifecycle fired around them


def test_run_sweep_batch_fallback_partitions_grid():
    """A grid with cells the batched engine cannot replay exactly raises by
    default; with on_unsupported="fallback" it is *partitioned* -- only the
    offending cells run on the sequential vector engine."""
    from repro.sim.batch import BatchUnsupported

    specs = [SweepSpec(name="a", n_hosts=4, spike="flat", duration_s=300.0,
                       tick_s=30.0),
             SweepSpec(name="b", n_hosts=4, spike="flat", duration_s=600.0,
                       tick_s=30.0)]         # mixed time grids
    with pytest.raises(BatchUnsupported, match="time grid"):
        run_sweep(specs, policies=("cpc",), engine="batch")
    with pytest.warns(RuntimeWarning, match="sequential vector engine"):
        res = run_sweep(specs, policies=("cpc",), engine="batch",
                        on_unsupported="fallback")
    assert set(res) == {"a", "b"}
    # Parity for both halves of the partition against the pure-vector run.
    for specs_one in ([specs[0]], [specs[1]]):
        ref = run_sweep(specs_one, policies=("cpc",), engine="vector")
        name = specs_one[0].name
        assert res[name]["cpc"].cap_changes == ref[name]["cpc"].cap_changes
        np.testing.assert_allclose(res[name]["cpc"].energy_j,
                                   ref[name]["cpc"].energy_j, rtol=1e-9)


@pytest.mark.parametrize("order", ("reversed", "shuffled"))
def test_run_sweep_async_completion_order_independent(monkeypatch, order):
    """The overlapped pipeline dispatches every bucket before harvesting
    any; out-of-order bucket completion (injected by shuffling the harvest
    order) must still return the merged grid in exact specs x policies
    order, with per-cell values matching the vector engine -- including the
    vector-fallback cells interleaved into the assembly."""
    import repro.sim.sweep as sw

    specs = [SweepSpec(name="small", n_hosts=4, spike="burst",
                       duration_s=600.0, tick_s=30.0),
             SweepSpec(name="big", n_hosts=8, spike="burst",
                       duration_s=600.0, tick_s=30.0),
             SweepSpec(name="odd", n_hosts=4, spike="flat",
                       duration_s=300.0, tick_s=30.0)]  # mixed time grid
    policies = ("cpc", "static")
    ref = run_sweep(specs, policies=policies, engine="vector")

    orders: list = []

    def scrambled(n):
        idx = list(range(n))
        if order == "reversed":
            idx.reverse()
        else:
            rng = np.random.RandomState(0)
            rng.shuffle(idx)
        orders.append(list(idx))
        return idx

    monkeypatch.setattr(sw, "_harvest_order", scrambled)
    with pytest.warns(RuntimeWarning, match="sequential vector engine"):
        res = run_sweep(specs, policies=policies, engine="batch",
                        on_unsupported="fallback")
    # The hetero grid really produced >= 2 concurrently dispatched buckets
    # (pow2 classes (4, 16) and (8, 16)) whose harvest we scrambled.
    assert orders and max(len(o) for o in orders) >= 2
    # Exact specs x policies iteration order, fallback cell included.
    assert list(res) == [s.name for s in specs]
    for name in res:
        assert list(res[name]) == list(policies)
    for s in specs:
        for p in policies:
            a, b = ref[s.name][p], res[s.name][p]
            assert b.cap_changes == a.cap_changes, (s.name, p)
            np.testing.assert_allclose(b.energy_j, a.energy_j, rtol=1e-9)
            np.testing.assert_allclose(b.cpu_payload_mhz_s,
                                       a.cpu_payload_mhz_s, rtol=1e-9)


_TS_FIELDS = ("cpu_payload_mhz_s", "cpu_demand_mhz_s", "mem_payload_mb_s",
              "mem_demand_mb_s", "energy_j")
_TS_COUNTERS = ("cap_changes", "vmotions", "power_ons", "power_offs")


@pytest.mark.parametrize("regime", ("cap", "dpm", "rules", "timed"))
def test_reduced_metrics_bit_identical_to_timeseries(regime):
    """The device-side reduced path (default) and the full per-tick
    timeseries path agree bit for bit: ``keep_timeseries=False`` summaries
    equal the ``keep_timeseries=True`` run's summaries *and* the
    ``fold_timeseries`` reduction of its per-tick series, across every
    batched regime (cap-only scan, DPM churn, rules + balancer, timed
    migrations)."""
    from repro.sim.batch import BatchedSimulator
    from repro.sim.sweep import _build_batch_cells, _grid_balancer

    grids = {
        "cap": dict(sizes=(4,), spikes=("burst",), heterogeneous=(False,),
                    duration_s=600.0, tick_s=30.0),
        "dpm": dict(sizes=(6,), spikes=("burst",), heterogeneous=(False,),
                    churns=("dpm",), duration_s=1500.0, tick_s=30.0),
        "rules": dict(sizes=(8,), spikes=("burst",), heterogeneous=(False,),
                      rules=("violation_burst",), duration_s=600.0,
                      tick_s=10.0),
        "timed": dict(sizes=(6,), spikes=("burst",), heterogeneous=(False,),
                      churns=("timed_churn",), rules=("violation_burst",),
                      duration_s=1200.0, tick_s=10.0),
    }
    specs = scenario_families(budgets_per_host_w=(250.0,), **grids[regime])
    cells, _ = _build_batch_cells(specs, ("cpc", "static"))
    bal = _grid_balancer(specs)
    r0 = BatchedSimulator(cells, balancer=bal, slot_slack=3.0).run()
    r1 = BatchedSimulator(cells, balancer=bal, slot_slack=3.0,
                          keep_timeseries=True).run()
    assert r0.timeseries is None
    assert set(r1.timeseries) == set(_TS_FIELDS) | set(_TS_COUNTERS)
    red = r1.reduced_timeseries()
    for f in _TS_FIELDS:
        assert np.array_equal(getattr(r1, f), getattr(r0, f)), f
        assert np.array_equal(red[f], getattr(r0, f)), f
    for f in _TS_COUNTERS:
        assert np.array_equal(getattr(r1, f), getattr(r0, f)), f
        assert np.array_equal(red[f], getattr(r0, f)), f
    # The satisfaction summary derives from the folded fields exactly too.
    with np.errstate(invalid="ignore"):
        np.testing.assert_array_equal(
            red["cpu_payload_mhz_s"] / red["cpu_demand_mhz_s"],
            r0.cpu_payload_mhz_s / r0.cpu_demand_mhz_s)
    # Each regime exercised the machinery whose counters it folds.
    if regime == "dpm":
        assert int(r0.power_offs.sum()) > 0
    if regime in ("rules", "timed"):
        assert int(r0.vmotions.sum()) > 0
    assert int(r0.cap_changes.sum()) > 0


# --------------------------------------------- budget-tree (row) families
def test_row_contention_specs_shapes():
    specs = row_contention_specs(sizes=(10, 100))
    assert [s.n_hosts for s in specs] == [10, 100]
    assert all(s.tree == "two_row" for s in specs)
    assert len({s.name for s in specs}) == len(specs)


def test_unknown_tree_rejected():
    with pytest.raises(ValueError, match="tree"):
        build_sweep(SweepSpec(name="t", tree="nope"), "cpc")


def test_build_sweep_two_row_deployment_respects_tree():
    """Deployment projects the initial caps under the row limits, so every
    engine starts from a tree-respecting state."""
    spec = row_contention_specs(sizes=(10,))[0]
    for policy in ("cpc", "static", "statichigh"):
        snap, _, _ = build_sweep(spec, policy)
        tree = snap.effective_tree()
        assert tree is not None
        caps = np.array([h.power_cap for h in snap.hosts.values()])
        on = np.array([h.powered_on for h in snap.hosts.values()])
        assert tree.max_overshoot(caps, on) <= 1e-6
        # Row 0's limit really undercuts its pro-rata share.
        assert tree.limit[1] == pytest.approx(
            TWO_ROW_LIMIT_FRAC * snap.power_budget)


def test_build_sweep_tree_preserves_rng_stream():
    """Adding the tree must not disturb the random draws: the tree-less
    spec with the same seed deploys the identical VM set and traces."""
    base = SweepSpec(name="t", n_hosts=10, spike="burst", seed=5)
    treed = SweepSpec(name="t", n_hosts=10, spike="burst", seed=5,
                      tree="two_row")
    a, ta, _ = build_sweep(base, "cpc")
    b, tb, _ = build_sweep(treed, "cpc")
    assert [v.vm_id for v in a.vms.values()] == \
        [v.vm_id for v in b.vms.values()]
    for vid in ta:
        assert ta[vid](50.0) == tb[vid](50.0)


def test_row_contention_batch_matches_vector():
    """Differential acceptance: the two_row grid is bit-identical between
    the batched scan (tree columns carried through lax.scan) and the
    sequential vector engine -- exact cap-change counts, tight-tolerance
    payload/energy."""
    specs = row_contention_specs(sizes=(10,), duration_s=600.0)
    policies = ("cpc", "static")
    seq = run_sweep(specs, policies=policies, engine="vector")
    bat = run_sweep(specs, policies=policies, engine="batch")
    for name in seq:
        for p in policies:
            a, b = seq[name][p], bat[name][p]
            assert b.cap_changes == a.cap_changes, (name, p)
            assert b.vmotions == 0
            np.testing.assert_allclose(b.cpu_payload_mhz_s,
                                       a.cpu_payload_mhz_s, rtol=1e-9)
            np.testing.assert_allclose(b.energy_j, a.energy_j, rtol=1e-9)
    assert seq[specs[0].name]["cpc"].cap_changes > 0


def test_row_contention_policy_separation():
    """The burst is concentrated under the binding row, so CPC's tree-aware
    redistribution recovers payload Static strands against the row limit."""
    specs = row_contention_specs(sizes=(10,), duration_s=600.0)
    res = run_sweep(specs, policies=("cpc", "static"), engine="batch")
    name = specs[0].name
    cpc, static = res[name]["cpc"], res[name]["static"]
    assert cpc.cap_changes > 0 and static.cap_changes == 0
    assert cpc.cpu_payload_mhz_s > static.cpu_payload_mhz_s * 1.001


def test_run_sweep_batched_policy_separation():
    """CPC beats Static under host-correlated bursts on the batch engine."""
    spec = SweepSpec(name="sep", n_hosts=12, vms_per_host=8, spike="burst",
                     duration_s=1200.0, tick_s=20.0, seed=3)
    res = run_sweep_batched([spec], policies=("cpc", "static"))
    cpc, static = res["sep"]["cpc"], res["sep"]["static"]
    assert cpc.cap_changes > 0
    assert static.cap_changes == 0
    assert cpc.cpu_satisfaction >= static.cpu_satisfaction - 1e-9


@pytest.mark.slow
def test_sweep_scale_thousand_hosts():
    """Acceptance: a 1,000-host / 10,000-VM cell runs end-to-end."""
    spec = SweepSpec(name="xl", n_hosts=1000, vms_per_host=10,
                     spike="burst", duration_s=600.0)
    r = run_cell(spec, "cpc")
    assert r.spec.n_vms == 10_000
    assert r.ticks == 60
    assert r.cpu_satisfaction > 0.5
    assert np.isfinite(r.energy_j)
