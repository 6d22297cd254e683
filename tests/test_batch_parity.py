"""Batched-engine parity: BatchedSimulator must reproduce VectorSimulator.

The jit-compiled grid engine replays the paper's three evaluation scenarios
(all three policies packed as one batch per scenario) in the cap-only
management regime the sweeps isolate (no DPM, no migration search) and must
match the NumPy vector engine cell by cell: exact cap-change counts, float
tolerance for the payload/energy integrals.  Capacity-churn parity pins the
full host-lifecycle protocol -- DPM power-off with evacuation, Powercap
Redistribution funding a burst-driven power-on, scripted power events --
with exact cap-change / power-on / power-off / vmotion counts.  Also covers
the JAX waterfill primitive against the NumPy one and the engine's packing
constraints.
"""

import numpy as np
import pytest

from repro.core.kernels import DPMParams
from repro.core.manager import CloudPowerCapManager, ManagerConfig
from repro.core.power_model import PAPER_HOST
from repro.drs import balancer as balancer_mod
from repro.drs import dpm as dpm_mod
from repro.drs.snapshot import ClusterSnapshot, Host, VirtualMachine
from repro.sim import workloads
from repro.sim.batch import BatchCell, BatchedSimulator, BatchUnsupported
from repro.sim.cluster import SimConfig
from repro.sim.engine import VectorSimulator
from repro.sim.experiments import POLICIES, SCENARIOS

FLOAT_FIELDS = ("cpu_payload_mhz_s", "cpu_demand_mhz_s", "mem_payload_mb_s",
                "mem_demand_mb_s", "energy_j")
INT_FIELDS = ("cap_changes", "vmotions", "power_ons", "power_offs")


def _cap_only_manager(policy: str) -> CloudPowerCapManager:
    """The sweep regime: powercap policy only, no DPM, no migration search."""
    cfg = ManagerConfig(powercap_enabled=(policy == "cpc"),
                        dpm_enabled=False)
    cfg.balancer = balancer_mod.BalancerConfig(max_moves=0)
    return CloudPowerCapManager(cfg)


def _scenario_pair(scenario: str):
    """(vector results by policy, one BatchedSimulator over all policies)."""
    refs, cells = {}, []
    for policy in POLICIES:
        snap, traces, cfg, window = SCENARIOS[scenario].build(policy)
        cfg.record_timeline = False
        sim = VectorSimulator(snap, _cap_only_manager(policy), traces, cfg,
                              window=window)
        refs[policy] = sim.run()
        snap2, traces2, cfg2, window2 = SCENARIOS[scenario].build(policy)
        cfg2.record_timeline = False
        cells.append(BatchCell(
            name=f"{scenario}/{policy}", snapshot=snap2, traces=traces2,
            config=cfg2, powercap_enabled=(policy == "cpc"), window=window2))
    return refs, BatchedSimulator(cells)


def _assert_cell_parity(ref, batch, i, rtol=1e-9):
    acc = batch.accumulators(i)
    assert acc.cap_changes == ref.acc.cap_changes
    for f in FLOAT_FIELDS:
        np.testing.assert_allclose(getattr(acc, f), getattr(ref.acc, f),
                                   rtol=rtol, err_msg=f)
    assert set(acc.tag_payload) == set(ref.acc.tag_payload)
    for tag in ref.acc.tag_payload:
        np.testing.assert_allclose(acc.tag_payload[tag],
                                   ref.acc.tag_payload[tag], rtol=rtol)
        np.testing.assert_allclose(acc.tag_demand[tag],
                                   ref.acc.tag_demand[tag], rtol=rtol)
    wacc = batch.window_accumulators(i)
    assert (wacc is None) == (ref.window_acc is None)
    if wacc is not None:
        for f in FLOAT_FIELDS:
            np.testing.assert_allclose(getattr(wacc, f),
                                       getattr(ref.window_acc, f),
                                       rtol=rtol, err_msg=f"window {f}")


@pytest.mark.parametrize("scenario", ("headroom", "standby"))
def test_paper_scenario_parity(scenario):
    refs, bsim = _scenario_pair(scenario)
    res = bsim.run()
    for i, policy in enumerate(POLICIES):
        _assert_cell_parity(refs[policy], res, i)
    if scenario == "headroom":
        # The spike must actually exercise the jitted cap pipeline (standby's
        # uniform step stays balanced, so zero cap changes is correct there).
        assert res.accumulators(POLICIES.index("cpc")).cap_changes > 0


@pytest.mark.slow
def test_flexible_scenario_parity():
    refs, bsim = _scenario_pair("flexible")
    res = bsim.run()
    for i, policy in enumerate(POLICIES):
        _assert_cell_parity(refs[policy], res, i)


# ------------------------------------------------------ capacity churn
def _churn_build(budget_per_host=300.0):
    """Paper-Sec.-V-C-style valley-then-burst on 3 hosts / 30 VMs with
    budget headroom: DPM consolidates and powers host0 off mid-run, the
    burst trips the power-on trigger, and Powercap Redistribution funds
    host0's return from the unallocated pool plus donors."""
    hosts = [Host(f"host{i}", PAPER_HOST, power_cap=250.0)
             for i in range(3)]
    vms, traces = [], {}
    for i in range(30):
        vm = VirtualMachine(vm_id=f"vm{i}", vcpus=1, memory_mb=8 * 1024,
                            host_id=f"host{i // 10}")
        vms.append(vm)
        traces[vm.vm_id] = workloads.step_trace([
            (0.0, 1200.0, 2 * 1024),
            (700.0, 300.0, 2 * 1024),
            (1400.0, 2400.0, 2 * 1024),
        ])
    snap = ClusterSnapshot(hosts, vms, power_budget=3 * budget_per_host)
    cfg = SimConfig(duration_s=2100.0, drs_first_at_s=300.0,
                    record_timeline=False, instant_migrations=True)
    return snap, traces, cfg


def _churn_manager(policy: str) -> CloudPowerCapManager:
    cfg = ManagerConfig(powercap_enabled=(policy == "cpc"),
                        dpm_enabled=True)
    cfg.dpm = dpm_mod.DPMConfig(stable_window_s=150.0)
    cfg.balancer = balancer_mod.BalancerConfig(max_moves=0)
    return CloudPowerCapManager(cfg)


def _churn_pair(policies=("cpc", "static")):
    refs, cells = {}, []
    for policy in policies:
        snap, traces, cfg = _churn_build()
        sim = VectorSimulator(snap, _churn_manager(policy), traces, cfg)
        refs[policy] = sim.run()
        snap2, traces2, cfg2 = _churn_build()
        cells.append(BatchCell(
            name=policy, snapshot=snap2, traces=traces2, config=cfg2,
            powercap_enabled=(policy == "cpc"), dpm_enabled=True))
    bsim = BatchedSimulator(cells, dpm=DPMParams(stable_window_s=150.0),
                            slot_slack=3.0)
    return refs, bsim


def test_churn_power_off_then_on_parity():
    """Acceptance: the power-off -> burst -> funded power-on lifecycle runs
    end-to-end in one jitted program with exact action-count and
    float-tolerance energy parity against VectorSimulator."""
    policies = ("cpc", "static")
    refs, bsim = _churn_pair(policies)
    res = bsim.run()
    for i, policy in enumerate(policies):
        ref, acc = refs[policy], res.accumulators(i)
        for f in INT_FIELDS:
            assert getattr(acc, f) == getattr(ref.acc, f), (policy, f)
        for f in FLOAT_FIELDS:
            np.testing.assert_allclose(getattr(acc, f),
                                       getattr(ref.acc, f),
                                       rtol=1e-9, err_msg=(policy, f))
    # The scenario must actually churn: a power-off AND a power-on, with
    # the cpc cell's power-on funded by emitted cap changes.
    cpc = res.accumulators(policies.index("cpc"))
    assert cpc.power_offs == 1 and cpc.power_ons == 1
    assert cpc.vmotions == 10           # host0's evacuation
    assert cpc.cap_changes > 0
    # host0 ends powered back on in both planes.
    assert bool(res.final_on[policies.index("cpc"), 0])
    assert refs["cpc"].final.hosts["host0"].powered_on


def test_churn_scripted_events_parity():
    """Scripted maintenance window (off at 700 s, back at 1400 s) replayed
    identically by both engines, without DPM."""
    refs, cells = {}, []
    for policy in ("cpc", "static"):
        snap, traces, cfg = _churn_build()
        cfg.power_events = ((700.0, "host1", False), (1400.0, "host1", True))
        sim = VectorSimulator(snap, _cap_only_manager(policy), traces, cfg)
        refs[policy] = sim.run()
        snap2, traces2, cfg2 = _churn_build()
        cfg2.power_events = cfg.power_events
        cells.append(BatchCell(
            name=policy, snapshot=snap2, traces=traces2, config=cfg2,
            powercap_enabled=(policy == "cpc")))
    res = BatchedSimulator(cells).run()
    for i, policy in enumerate(("cpc", "static")):
        ref, acc = refs[policy], res.accumulators(i)
        for f in INT_FIELDS:
            assert getattr(acc, f) == getattr(ref.acc, f), (policy, f)
        for f in FLOAT_FIELDS:
            np.testing.assert_allclose(getattr(acc, f),
                                       getattr(ref.acc, f),
                                       rtol=1e-9, err_msg=(policy, f))
        assert bool(res.final_on[i, 1])      # host1 came back


def test_churn_event_boot_during_pending_power_off_parity():
    """A scripted power-on that fires while a DPM power-off's deferred cap
    actions are pending: the booted host's (clamped) cap must survive the
    deferred application -- only hosts with emitted actions change."""
    refs, cells = {}, []
    for policy in ("cpc", "static"):
        snaps = []
        for _ in range(2):
            snap, traces, cfg = _churn_build()
            # A 4th standby host that a scripted event boots at 920 s --
            # inside the [900, 930) pending window of the DPM power-off
            # the valley triggers at the 900 s DRS tick.
            snap.hosts["spare"] = Host("spare", PAPER_HOST,
                                       power_cap=120.0, powered_on=False)
            cfg.power_events = ((920.0, "spare", True),)
            snaps.append((snap, traces, cfg))
        snap, traces, cfg = snaps[0]
        sim = VectorSimulator(snap, _churn_manager(policy), traces, cfg)
        refs[policy] = sim.run()
        snap2, traces2, cfg2 = snaps[1]
        cells.append(BatchCell(
            name=policy, snapshot=snap2, traces=traces2, config=cfg2,
            powercap_enabled=(policy == "cpc"), dpm_enabled=True))
    res = BatchedSimulator(cells, dpm=DPMParams(stable_window_s=150.0),
                           slot_slack=3.0).run()
    for i, policy in enumerate(("cpc", "static")):
        ref, acc = refs[policy], res.accumulators(i)
        assert ref.acc.power_offs >= 1          # the window was live
        for f in INT_FIELDS:
            assert getattr(acc, f) == getattr(ref.acc, f), (policy, f)
        for f in FLOAT_FIELDS:
            np.testing.assert_allclose(getattr(acc, f),
                                       getattr(ref.acc, f),
                                       rtol=1e-9, err_msg=(policy, f))
        np.testing.assert_allclose(
            res.final_caps[i, 3],
            refs[policy].final.hosts["spare"].power_cap, rtol=1e-9)


def test_dpm_cell_timed_requires_launch_gating():
    """Timed migrations batch fine, but only under the gated launch
    protocol -- an ungated timed cell (no slot or bandwidth limits) is
    rejected loudly so it falls back to the vector engine."""
    snap, traces, cfg = _churn_build()
    cfg.instant_migrations = False
    with pytest.raises(BatchUnsupported, match="launch gating"):
        BatchedSimulator([BatchCell("a", snap, traces, cfg,
                                    dpm_enabled=True)])


def test_slot_pressure_raises_instead_of_diverging():
    """A slot axis too tight for the consolidation the scenario performs
    must fail loudly, not silently diverge from the object plane."""
    snap, traces, cfg = _churn_build()
    cells = [BatchCell("a", snap, traces, cfg, powercap_enabled=True,
                       dpm_enabled=True)]
    bsim = BatchedSimulator(cells, dpm=DPMParams(stable_window_s=150.0),
                            slot_slack=1.0)
    with pytest.raises(RuntimeError, match="slot_slack"):
        bsim.run()


def test_batch_requires_uniform_time_grid():
    snap, traces, cfg, window = SCENARIOS["headroom"].build("cpc")
    snap2, traces2, cfg2, _ = SCENARIOS["headroom"].build("static")
    cfg2.tick_s = cfg.tick_s * 2
    cells = [BatchCell("a", snap, traces, cfg, window=window),
             BatchCell("b", snap2, traces2, cfg2)]
    with pytest.raises(ValueError, match="time grid"):
        BatchedSimulator(cells)


def test_batch_rejects_spec_less_traces():
    snap, traces, cfg, _ = SCENARIOS["headroom"].build("cpc")
    traces["vm0"] = lambda t: (1000.0, 2048.0)   # no declarative spec
    with pytest.raises(ValueError, match="declarative spec"):
        BatchedSimulator([BatchCell("a", snap, traces, cfg)])


def test_jax_waterfill_matches_numpy():
    import jax

    from repro.drs.entitlement import batched_waterfill, jax_batched_waterfill
    rng = np.random.RandomState(7)
    n_segs = 5
    caps = rng.uniform(0.0, 30000.0, n_segs)
    floors, ceils, weights, seg = [], [], [], []
    for s in range(n_segs):
        k = rng.randint(1, 12)
        f = rng.uniform(0.0, 3000.0, k)
        floors.append(f)
        ceils.append(f + rng.uniform(0.0, 9000.0, k))
        weights.append(rng.uniform(1.0, 4000.0, k))
        seg.append(np.full(k, s, dtype=np.int64))
    floors, ceils, weights, seg = map(
        np.concatenate, (floors, ceils, weights, seg))
    ref = batched_waterfill(caps, floors, ceils, weights, seg, n_segs)
    with jax.enable_x64(True):
        got = np.asarray(jax_batched_waterfill(caps, floors, ceils, weights,
                                               seg, n_segs))
    np.testing.assert_allclose(got, ref, rtol=1e-9, atol=1e-9)


def _step_functions(k, rng, n):
    """``n`` random step functions of at most ``k`` segments in TraceBank's
    layout: integer breakpoints (so ``mod`` is exact and phases can land on
    them), ``inf``-padded past each slot's segment count, every other slot
    periodic, values with ``-0.0`` and ``inf`` among them."""
    gaps = rng.randint(1, 5, (n, k)) * 10.0
    gaps[:, 0] = rng.randint(0, 3, n) * 10.0         # first breakpoint >= 0
    bps = np.cumsum(gaps, axis=1)
    n_segs = rng.randint(1, k + 1, n)
    bps[np.arange(k)[None, :] >= n_segs[:, None]] = np.inf
    last = bps[np.arange(n), n_segs - 1]
    period = np.where(np.arange(n) % 2 == 0,
                      last + rng.randint(1, 4, n) * 10.0, np.inf)
    cpu, mem = rng.uniform(0.0, 3000.0, (2, n, k))
    cpu[rng.rand(n, k) < 0.1] = -0.0
    mem[rng.rand(n, k) < 0.1] = np.inf
    finite = np.unique(bps[np.isfinite(bps)])
    laps = np.unique(period[np.isfinite(period)])[:, None] * (1.0, 2.0)
    ts = np.concatenate([[0.0, 5.0, 1234.5], finite, finite + 0.5,
                         laps.ravel(), (laps[:, :, None] + finite).ravel()])
    return period, bps, cpu, mem, np.unique(ts)


@pytest.mark.parametrize("k", (1, 2, 3, 4))
def test_trace_lookup_matches_gather_bit_for_bit(k):
    """The scan's select over the segment axis returns exactly what a
    ``take_along_axis`` gather and ``TraceBank.eval`` return, at every
    phase: on a breakpoint, between, before the first, past a period."""
    import jax
    import jax.numpy as jnp

    from repro.sim.batch import trace_demands

    shape = (3, 4, 5)                                 # cells, hosts, slots
    rng = np.random.RandomState(100 + k)
    period, bps, cpu, mem, ts = _step_functions(k, rng, int(np.prod(shape)))
    bank = workloads.TraceBank([f"vm{i}" for i in range(len(period))])
    bank.rows = np.arange(len(period))
    bank.period, bank.bps, bank.cpu_vals, bank.mem_vals = period, bps, cpu, mem

    def gather(tr, t):
        phase = jnp.where(jnp.isfinite(tr["period"]),
                          jnp.mod(t, tr["period"]), t)
        idx = jnp.clip(jnp.sum(tr["bps"] <= phase[..., None], axis=-1) - 1,
                       0, None)
        return tuple(jnp.take_along_axis(tr[c], idx[..., None], axis=-1)
                     [..., 0] for c in ("cpu_vals", "mem_vals"))

    tr = {"period": period.reshape(shape),
          "bps": bps.reshape(shape + (k,)),
          "cpu_vals": cpu.reshape(shape + (k,)),
          "mem_vals": mem.reshape(shape + (k,))}

    def bits(x):
        return np.asarray(x, dtype=np.float64).reshape(-1).view(np.uint64)

    with jax.enable_x64(True):
        tr = {c: jnp.asarray(v) for c, v in tr.items()}
        select, take = jax.jit(trace_demands), jax.jit(gather)
        for t in ts:
            got = select(tr, jnp.float64(t))
            want = take(tr, jnp.float64(t))
            rows, b_cpu, b_mem = bank.eval(float(t))
            assert (rows == np.arange(len(period))).all()
            for g, w, b in zip(got, want, (b_cpu, b_mem)):
                assert (bits(g) == bits(w)).all(), t
                assert (bits(g) == bits(b)).all(), t
