"""DPM evacuation on the batched engine: the planner's visit order, the
full-size clusters where slot order once decided it, and the in-scan
counters of the dynamic program.

The object plane (``repro.drs.dpm.run_dpm``) and the plain reference
(``bench/reference``) evacuate the victim's VMs by memory descending, ties
in VM-index order.  The batched engine keeps VMs in slots; after a
first-free placement lands a VM on a host, slot order is not VM order, so
``kernels.plan_evacuation`` takes the VM index from the ``vm`` slot column.
"""

import pathlib
import sys

import numpy as np
import pytest

from repro.backend import NUMPY, jax_backend
from repro.core import kernels
from repro.sim import batch, cluster
from repro.sim import sweep as sw

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _victim_case(xp):
    """Three identical hosts at full cap (1000 MHz managed, 10 GB).  Host 0
    is the victim: slots hold VMs 7, 2, 5 (1 GB each) and 9 (3 GB), so the
    equal-memory VMs sit out of VM-index order.  Hosts 1 and 2 carry 200
    and 210 MHz."""
    on = np.ones((1, 3), dtype=bool)
    hosts = kernels.HostCols(xp.asarray(on), xp.full((1, 3), 100.0),
                             xp.full((1, 3), 200.0),
                             xp.full((1, 3), 1000.0), xp.zeros((1, 3)))
    occ = np.zeros((1, 3, 5), dtype=bool)
    vm = np.full((1, 3, 5), -1, dtype=np.int64)
    eff = np.zeros((1, 3, 5))
    mem = np.zeros((1, 3, 5))
    for j, (v, e, m) in enumerate([(7, 100.0, 1e3), (2, 150.0, 1e3),
                                   (5, 120.0, 1e3), (9, 50.0, 3e3)]):
        occ[0, 0, j], vm[0, 0, j], eff[0, 0, j], mem[0, 0, j] = True, v, e, m
    for h, (v, e) in ((1, (0, 200.0)), (2, (1, 210.0))):
        occ[0, h, 0], vm[0, h, 0], eff[0, h, 0], mem[0, h, 0] = True, v, e, 1e3
    return dict(hosts=hosts, caps=xp.full((1, 3), 200.0),
                victim=xp.asarray(np.zeros(1, dtype=np.int64)),
                occ=xp.asarray(occ), vm=xp.asarray(vm),
                eff_slot=xp.asarray(eff), mem_slot=xp.asarray(mem),
                res_slot=xp.zeros((1, 3, 5)),
                migratable=xp.ones((1, 3, 5), dtype=bool),
                host_mem=xp.full((1, 3), 1e4), target_util=0.8), vm


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_evacuation_visits_equal_memory_vms_in_vm_index_order(backend):
    """The 3 GB VM 9 leaves first, then VMs 2, 5, 7 (equal memory) in
    VM-index order, each to the cooler host at that point.  Visited in slot
    order (7, 2, 5) the greedy sends VM 2 to host 1 and VM 5 to host 2."""
    import jax
    be = NUMPY if backend == "numpy" else jax_backend()
    with jax.enable_x64(True):
        args, vm = _victim_case(be.xp)
        ok, order, dests, n_evac, pressure, trips = kernels.plan_evacuation(
            be, **args)
        order, dests = np.asarray(order)[0], np.asarray(dests)[0]
    assert bool(np.asarray(ok)[0]) and int(np.asarray(n_evac)[0]) == 4
    assert not bool(np.asarray(pressure)[0])
    assert trips == 5
    assert list(vm[0, 0, order]) == [9, 2, 5, 7, -1]
    assert {int(vm[0, 0, order[k]]): int(dests[k]) for k in range(4)} == \
        {9: 1, 2: 2, 5: 1, 7: 2}
    assert dests[4] == -1


def _reference_cell(seed):
    return {"name": f"h64_burst_dpm_s{seed}", "policy": "statichigh",
            "n_hosts": 64, "vms_per_host": 10, "rack_budget_w": 250.0 * 64,
            "spike": "burst", "heterogeneous": False, "churn": "dpm",
            "duration_s": 3600.0, "tick_s": 10.0, "drs_period_s": 300.0,
            "seed": seed}


@pytest.mark.parametrize("seed", [483869366, 1315599579, 1009528420,
                                  10000047])
def test_statichigh_dpm_cluster_matches_the_reference(seed):
    """Homogeneous ``statichigh`` DPM clusters of the benchmark's
    ``dpm_valley`` cell at full size (64 hosts, 360 ticks) whose second
    power-off (t = 900 s) evacuated VMs in slot order and so sent them to
    other hosts; the fourth then picked another victim, and the vMotion
    counts parted from the reference's."""
    from bench.reference.scenario import COUNTS, run_cell
    from bench.tests.cells import load
    _, config, _ = load("dpm_valley")
    cell = _reference_cell(seed)
    want = run_cell(cell, config)
    spec = sw.SweepSpec(**{k: v for k, v in cell.items() if k != "policy"})
    got = sw.run_sweep([spec], policies=["statichigh"], engine="batch",
                       n_devices=1)[spec.name]["statichigh"]
    assert {k: getattr(got, k) for k in COUNTS} == \
        {k: want[k] for k in COUNTS}
    assert want["power_offs"] > 0
    assert got.energy_j == pytest.approx(want["energy_j"], rel=1e-12)


def _sim(specs, policies):
    cells, _ = sw._build_batch_cells(specs, policies)
    return batch.BatchedSimulator(cells, balancer=sw._grid_balancer(specs),
                                  n_devices=1)


def test_dpm_counters_match_the_object_simulator(monkeypatch):
    """A small DPM grid: the scan's ``power_offs`` and ``power_ons`` are the
    object ``Simulator``'s power events summed over cells, and
    ``evac_trips`` is the slot width ``J`` for every tick at which some
    cell invoked the manager (the union of the cells' invocation times)."""
    specs = [sw.SweepSpec(name=f"h8_dpm_{het}", n_hosts=8, spike="burst",
                          heterogeneous=het, churn="dpm", duration_s=3600.0,
                          seed=3) for het in (False, True)]
    policies = ["cpc", "statichigh"]
    sim = _sim(specs, policies)
    res = sim.run()

    times = set()
    original = cluster.Simulator._invoke_manager

    def invoke(self, t):
        times.add(t)
        return original(self, t)

    monkeypatch.setattr(cluster.Simulator, "_invoke_manager", invoke)
    objs = [sw.run_cell(s, p, engine="legacy") for s in specs
            for p in policies]
    c = res.counters
    assert set(c) == {"balance_trips", "drs_invocations", "evac_trips",
                      "power_offs", "power_ons"}
    assert c["power_offs"] == sum(o.power_offs for o in objs) > 0
    assert c["power_ons"] == sum(o.power_ons for o in objs) > 0
    assert c["power_offs"] == int(res.power_offs.sum())
    j = sim._arrays["occ"].shape[-1]
    assert c["evac_trips"] == j * len(times)


def test_cap_only_program_keeps_its_counters_and_outputs():
    """The static program gains no output and no counter."""
    import jax
    spec = sw.SweepSpec(name="h8", n_hosts=8, spike="burst",
                        duration_s=900.0)
    sim = _sim([spec], ["cpc"])
    assert not sim._static.churn
    static, n_dev, a, _ = sim._prepare()
    with jax.enable_x64(True):
        out = jax.eval_shape(batch._build_program(static), a)
    assert "evac_trips" not in out
    assert "evac_trips" not in batch._out_specs(static,
                                                jax.sharding.PartitionSpec)
    assert set(sim.run().counters) == {"balance_trips", "drs_invocations"}
