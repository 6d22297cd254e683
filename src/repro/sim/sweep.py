"""Scenario-sweep harness: programmatic scenario families at cluster scale.

The paper evaluates CloudPowerCap on 3 hosts / 30 VMs; this module
generates whole families of scenarios -- cluster size x rack budget x
spike pattern x host-spec mix x capacity churn x placement rules -- and
runs each policy on the vectorized engine, reporting throughput
(ticks/sec) alongside the paper's payload / power metrics.  It feeds the
``sweep_scale`` / ``sweep_grid`` / ``sweep_grid_dpm`` /
``sweep_grid_rules`` / ``sweep_grid_timed`` / ``sweep_scale_sharded``
benchmark entries (``python -m benchmarks.run``).

Design notes:
  * Migration *search* stays disabled in the cap-only/churn families
    (``max_moves=0``): there the interesting regimes are cap-only
    management and capacity churn (cf. prediction-based oversubscription
    at Azure).  The *rule* families turn the full migration layer on --
    constraint correction plus the hill-climb balancer
    (:data:`RULE_BALANCER`) -- now that it runs as batched kernels
    (``sweep_grid_rules``).
  * Capacity-churn families (``SweepSpec.churn``) exercise the host
    lifecycle: ``dpm`` (a demand valley consolidates and powers a host
    off, a later burst powers it back on with Powercap Redistribution
    funding the cap), ``maintenance`` (a scripted power-off/power-on
    window), and ``failure`` (a scripted power-off that stays down, with
    DPM free to bring capacity back).  Those three run with instantaneous
    migrations; ``timed_churn`` / ``failure_cascade`` rerun the dpm /
    failure scenarios under the *timed* gated vMotion model (copy windows
    of at least one tick, both endpoints charged overhead, per-host slot
    and cluster bandwidth launch limits) with the full migration layer
    on, so deferred moves cascade across invocations -- the
    production-realistic churn regime.  All families replay identically
    on every engine.
  * Scenarios use zero reservations and default shares so admission
    control stays trivial and the sweep isolates powercap behavior.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import pathlib
import time
import warnings
from typing import Optional, Sequence

import numpy as np

from repro.core.manager import CloudPowerCapManager, ManagerConfig
from repro.core.power_model import PAPER_HOST, HostPowerSpec
from repro.drs import balancer as balancer_mod
from repro.drs.rules import AffinityRule, AntiAffinityRule, VMHostRule
from repro.drs.snapshot import ClusterSnapshot, Host, VirtualMachine
from repro.sim.cluster import SimConfig
from repro.sim.experiments import ENGINES, POLICIES
from repro.sim import workloads
from repro.sim.spans import next_sweep_id, span

# A smaller, less efficient host mixed in for heterogeneous sweeps:
# 8 cores x 2.4 GHz, 64 GB, idle 120 W / peak 240 W.
SMALL_HOST = HostPowerSpec(
    capacity_peak=19_200.0,
    power_idle=120.0,
    power_peak=240.0,
    power_nameplate=300.0,
    memory_mb=64 * 1024,
)

SPIKES = ("flat", "burst", "step", "prime")
CHURNS = ("none", "dpm", "maintenance", "failure", "timed_churn",
          "failure_cascade")
RULESETS = ("none", "violation_burst", "cap_blocked")
TREES = ("none", "two_row")

#: ``two_row`` tree family: row 0 (the first half of the hosts) is limited
#: to this fraction of the rack budget -- below its pro-rata share, so the
#: row limit binds before the rack budget does.  The burst is concentrated
#: on row 0 (see :func:`build_sweep`), so CloudPowerCap must redistribute
#: *within* the binding row; Static strands the capacity.
TWO_ROW_LIMIT_FRAC = 0.45

#: Launch gating for the timed-vMotion churn families: per-host concurrent
#: migration slots and a cluster-wide launches-per-invocation budget.
#: Deferred moves are re-scored at the next invocation (cascading churn).
TIMED_SLOTS_PER_HOST = 2
TIMED_BANDWIDTH = 8

#: The migration balancer used by rule-scenario cells, on every engine (the
#: object manager for vector cells, ``kernels.MigrationParams`` for the
#: batched program); non-rule sweep cells keep migration search disabled.
RULE_BALANCER = balancer_mod.BalancerConfig(max_moves=8)


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """One cell of the scenario grid."""

    name: str
    n_hosts: int = 10
    vms_per_host: int = 10
    rack_budget_w: Optional[float] = None   # default: 250 W per host
    spike: str = "burst"                    # one of SPIKES
    heterogeneous: bool = False             # mix PAPER_HOST with SMALL_HOST
    churn: str = "none"                     # one of CHURNS
    rules: str = "none"                     # one of RULESETS
    tree: str = "none"                      # one of TREES
    duration_s: float = 1200.0
    tick_s: float = 10.0
    drs_period_s: float = 300.0
    seed: int = 0

    @property
    def budget(self) -> float:
        return (self.rack_budget_w if self.rack_budget_w is not None
                else 250.0 * self.n_hosts)

    @property
    def n_vms(self) -> int:
        return self.n_hosts * self.vms_per_host

    @property
    def dpm_enabled(self) -> bool:
        """Churn families where the manager itself drives the lifecycle."""
        return self.churn in ("dpm", "failure", "timed_churn",
                              "failure_cascade")

    @property
    def timed(self) -> bool:
        """Families running the timed (gated) vMotion execution model:
        migrations occupy a copy window, both endpoints burn overhead, and
        per-host slot / cluster bandwidth limits gate launches."""
        return self.churn in ("timed_churn", "failure_cascade")

    @property
    def migration_enabled(self) -> bool:
        """Rule families run the migration layer (correction + balancer);
        the timed churn families always do -- deferred moves re-scored
        across invocations are the point."""
        return self.rules != "none" or self.timed


def _specs_for(spec: SweepSpec) -> list[HostPowerSpec]:
    if not spec.heterogeneous:
        return [PAPER_HOST] * spec.n_hosts
    return [PAPER_HOST if i % 2 == 0 else SMALL_HOST
            for i in range(spec.n_hosts)]


def _sweep_traces(spec: SweepSpec, base: np.ndarray, hot_host: np.ndarray,
                  phase_frac: np.ndarray, n_on: int,
                  vm_ids: Sequence[str]) -> workloads.TraceTable:
    """Vectorized demand-trace construction for one cell.

    Builds the whole cluster's ``(t0, cpu, mem)`` segment table as array
    ops -- per-VM factory calls dominated end-to-end cell construction at
    sweep scale.  The batched engine packs the table straight into a
    ``TraceBank``; :func:`build_sweep` turns it into per-VM traces, whose
    values are IEEE-identical to the scalar factories the loop used to
    call.
    """
    n, d = spec.n_vms, spec.duration_s
    mem = 2 * 1024.0
    segs = np.zeros((n, 3, 3))
    segs[:, :, 2] = mem
    segs[:, 0, 1] = base
    counts = np.ones(n, dtype=np.int64)
    periods = np.full(n, np.inf)
    if spec.churn in ("dpm", "timed_churn"):
        # Valley-then-burst: the middle third idles the cluster into
        # DPM's power-off band; the final third runs hot enough to trip
        # the power-on trigger, so Powercap Redistribution must free a
        # consolidating host's budget and later fund its return.
        counts[:] = 3
        segs[:, 1, 0] = d / 3.0
        segs[:, 1, 1] = 0.2 * base
        segs[:, 2, 0] = 2.0 * d / 3.0
        segs[:, 2, 1] = 2.2 * base + 1500.0
    elif spec.spike == "flat":
        pass
    elif spec.spike == "burst":
        # VMs on ~20% of hosts spike >2x in the middle third of the run.
        hot = hot_host[np.arange(n) % n_on]
        counts[hot] = 3
        segs[hot, 1, 0] = d / 3.0
        segs[hot, 1, 1] = 2.0 * base[hot] + 1200.0
        segs[hot, 2, 0] = 2.0 * d / 3.0
        segs[hot, 2, 1] = base[hot]
    elif spec.spike == "step":
        # Cluster-wide step down then back up (standby-style).
        counts[:] = 3
        segs[:, 1, 0] = d / 3.0
        segs[:, 1, 1] = base / 3.0
        segs[:, 2, 0] = 2.0 * d / 3.0
        segs[:, 2, 1] = base
    else:  # prime: periodic off/prime/off window, phase drawn per VM
        periods[:] = d
        off, prime = 0.3 * base, 2.2 * base
        counts[:] = 3
        segs[:, 0, 1] = off
        segs[:, 1, 0] = phase_frac * d
        segs[:, 1, 1] = prime
        segs[:, 2, 0] = (phase_frac + 0.4) * d
        segs[:, 2, 1] = off
        z = phase_frac <= 0.0        # measure-zero draw: window opens at 0
        if z.any():
            counts[z] = 2
            segs[z, 0, 1] = prime[z]
            segs[z, 1, 0] = (phase_frac[z] + 0.4) * d
            segs[z, 1, 1] = off[z]
    return workloads.TraceTable(tuple(vm_ids), segs, counts, periods)


def build_sweep(spec: SweepSpec, policy: str,
                vm_memo: Optional[dict] = None
                ) -> tuple[ClusterSnapshot, dict, SimConfig]:
    """Materialize one (spec, policy) cell: its snapshot, ``{vm_id:
    trace}`` and config.

    Deployment mirrors paper Table II: ``cpc``/``static`` spread the rack
    budget across every host; ``statichigh`` runs fewer hosts at their
    physical peak (the rest stay in standby with a zero cap).

    ``vm_memo`` (scoped to one grid) shares the ``VirtualMachine`` list
    across every cell with the same (VM count, powered-on host sequence)
    -- the only facts the list depends on -- so a whole grid builds its
    VM objects once.  Callers passing it promise the returned snapshot is
    treated read-only (true for the batched engine, which only packs);
    cells that customize VMs (the ``cap_blocked`` reservations) replace
    the affected entries copy-on-write instead of mutating.
    """
    snap, table, cfg = _build_cell(spec, policy, vm_memo)
    return snap, table.traces(), cfg


def _build_cell(spec: SweepSpec, policy: str,
                vm_memo: Optional[dict] = None
                ) -> tuple[ClusterSnapshot, workloads.TraceTable, SimConfig]:
    """:func:`build_sweep` with the cluster's demand as its segment table."""
    if spec.spike not in SPIKES:
        raise ValueError(f"unknown spike pattern {spec.spike!r}")
    if spec.churn not in CHURNS:
        raise ValueError(f"unknown churn family {spec.churn!r}")
    if spec.rules not in RULESETS:
        raise ValueError(f"unknown rule family {spec.rules!r}")
    if spec.tree not in TREES:
        raise ValueError(f"unknown tree family {spec.tree!r}")
    host_specs = _specs_for(spec)
    budget = spec.budget
    total_peak = sum(s.power_peak for s in host_specs)

    hosts: list[Host] = []
    if policy == "statichigh":
        # Peak caps until the budget is exhausted.
        spent = 0.0
        for i, s in enumerate(host_specs):
            on = spent + s.power_peak <= budget + 1e-9
            hosts.append(Host(host_id=f"host{i}", spec=s,
                              power_cap=s.power_peak if on else 0.0,
                              powered_on=on))
            if on:
                spent += s.power_peak
    else:
        # Budget split pro-rata by peak power (uniform for homogeneous).
        for i, s in enumerate(host_specs):
            cap = budget * s.power_peak / total_peak
            hosts.append(Host(host_id=f"host{i}", spec=s,
                              power_cap=min(cap, s.power_peak)))
    on_hosts = [h.host_id for h in hosts if h.powered_on]
    if not on_hosts:
        raise ValueError("budget too small: no host can power on")

    rng = np.random.RandomState(spec.seed)
    base = rng.uniform(600.0, 1400.0, size=spec.n_vms)
    # Bursts are host-correlated (like the paper's headroom scenario): every
    # VM on a "hot" host spikes together, so static caps actually strand
    # capacity and the policies separate.
    hot_host = rng.rand(spec.n_hosts) < 0.2
    phase_frac = rng.uniform(0.0, 0.5, size=spec.n_vms)
    if spec.tree == "two_row":
        # Concentrate the burst on row 0 so its limit is what binds (the
        # random draws above still happen, keeping the stream identical
        # for tree-less specs with the same seed).
        hot_host = np.zeros(spec.n_hosts, dtype=bool)
        hot_host[:max(spec.n_hosts // 4, 1)] = True

    n_on = len(on_hosts)
    vm_key = (spec.n_vms, tuple(on_hosts))
    vms = None if vm_memo is None else vm_memo.get(vm_key)
    if vms is None:
        vms = [VirtualMachine(vm_id=f"vm{v}", vcpus=1, memory_mb=8 * 1024,
                              host_id=on_hosts[v % n_on])
               for v in range(spec.n_vms)]
        if vm_memo is not None:
            vm_memo[vm_key] = vms
    table = _sweep_traces(spec, base, hot_host, phase_frac, n_on,
                          [vm.vm_id for vm in vms])

    rules: list = []
    if spec.rules != "none":
        on_count = len(on_hosts)
        if on_count < 4:
            raise ValueError("rule families need >= 4 powered-on hosts")
        if spec.rules == "violation_burst":
            # A burst of corrections for the first DRS invocation: two
            # affinity groups split across hosts, two anti-affinity pairs
            # co-placed, two VMs parked off their allowed hosts.
            rules = [
                AffinityRule(("vm0", "vm1")),
                AffinityRule(("vm2", "vm3")),
                AntiAffinityRule(("vm4", f"vm{4 + on_count}")),
                AntiAffinityRule(("vm5", f"vm{5 + on_count}")),
                VMHostRule("vm6", frozenset(
                    {on_hosts[7 % on_count], on_hosts[8 % on_count]})),
                VMHostRule("vm7", frozenset(
                    {on_hosts[8 % on_count], on_hosts[9 % on_count]})),
            ]
        else:  # cap_blocked -- paper Fig. 1a at sweep scale
            # Affinity correction whose fit only passes when the check
            # sees *fundable* capacity: the anchor host must reach beyond
            # its current cap (CloudPowerCap corrects; Static cannot).
            anchor, mover = "vm2", "vm0"
            filler = f"vm{on_count}"            # second VM on host 0
            overrides = {anchor: 14_000.0, mover: 6_000.0,
                         filler: 12_000.0}
            if vm_memo is None:
                vm_by_id = {v.vm_id: v for v in vms}
                for vid, res in overrides.items():
                    vm_by_id[vid].reservation = res
            else:
                # The memoized list is shared across cells: replace the
                # customized VMs copy-on-write, never mutate in place.
                vms = [dataclasses.replace(v, reservation=overrides[v.vm_id])
                       if v.vm_id in overrides else v for v in vms]
            rules = [AffinityRule((mover, anchor))]
    tree = None
    if spec.tree == "two_row":
        from repro.core.budget_tree import BudgetTree
        tree = BudgetTree.two_rows(budget, spec.n_hosts,
                                   row0_limit=TWO_ROW_LIMIT_FRAC * budget)
        # Deployment must respect the tree from t=0: scale each binding
        # row's initial caps down to its limit (zero floors -- sweep VMs
        # carry no reservations).
        caps = np.array([h.power_cap for h in hosts])
        on_mask = np.array([h.powered_on for h in hosts])
        caps = tree.project(caps, on_mask, floors=np.zeros(spec.n_hosts))
        for h, cap in zip(hosts, caps):
            h.power_cap = float(cap)
    snap = ClusterSnapshot(hosts, vms, power_budget=budget, rules=rules,
                           budget_tree=tree)
    power_events: tuple = ()
    if spec.churn == "maintenance":
        # One powered-on host leaves for the middle third and returns.
        power_events = ((spec.duration_s / 3.0, on_hosts[0], False),
                        (2.0 * spec.duration_s / 3.0, on_hosts[0], True))
    elif spec.churn in ("failure", "failure_cascade"):
        # Abrupt capacity loss at mid-run; DPM may repair it.  In the
        # cascade family the repair happens under timed gated migrations,
        # so the rebalancing churn spreads across invocations.
        power_events = ((spec.duration_s / 2.0, on_hosts[0], False),)
    cfg = SimConfig(duration_s=spec.duration_s, tick_s=spec.tick_s,
                    drs_period_s=spec.drs_period_s,
                    drs_first_at_s=spec.drs_period_s,
                    record_timeline=False,
                    instant_migrations=((spec.dpm_enabled
                                         or spec.migration_enabled)
                                        and not spec.timed),
                    migration_slots_per_host=(TIMED_SLOTS_PER_HOST
                                              if spec.timed else None),
                    migration_bandwidth=(TIMED_BANDWIDTH
                                         if spec.timed else None),
                    power_events=power_events)
    return snap, table, cfg


def _sweep_manager(policy: str,
                   spec: Optional[SweepSpec] = None) -> CloudPowerCapManager:
    cfg = ManagerConfig(powercap_enabled=(policy == "cpc"),
                        dpm_enabled=bool(spec and spec.dpm_enabled))
    if spec is not None and spec.migration_enabled:
        # Rule families exercise the full migration layer: constraint
        # correction plus the hill-climb balancer.
        cfg.balancer = dataclasses.replace(RULE_BALANCER)
    else:
        # No migration *search* at scale (see module note); DPM's targeted
        # evacuations still run for the churn families.
        cfg.balancer = balancer_mod.BalancerConfig(max_moves=0)
    return CloudPowerCapManager(cfg)


@dataclasses.dataclass
class SweepCellResult:
    spec: SweepSpec
    policy: str
    wall_s: float                # batch engine: share of the batch's wall
    ticks: int
    ticks_per_s: float
    cpu_satisfaction: float
    cpu_payload_mhz_s: float
    energy_j: float
    cap_changes: int
    vmotions: int
    power_ons: int = 0
    power_offs: int = 0


def run_cell(spec: SweepSpec, policy: str,
             engine: str = "vector") -> SweepCellResult:
    snap, traces, cfg = build_sweep(spec, policy)
    manager = _sweep_manager(policy, spec)
    sim = ENGINES[engine](snap, manager, traces, cfg)
    t0 = time.perf_counter()
    result = sim.run()
    wall = time.perf_counter() - t0
    ticks = int(round(cfg.duration_s / cfg.tick_s))
    acc = result.acc
    return SweepCellResult(
        spec=spec, policy=policy, wall_s=wall, ticks=ticks,
        ticks_per_s=ticks / max(wall, 1e-9),
        cpu_satisfaction=acc.cpu_satisfaction(),
        cpu_payload_mhz_s=acc.cpu_payload_mhz_s,
        energy_j=acc.energy_j,
        cap_changes=acc.cap_changes,
        vmotions=acc.vmotions,
        power_ons=acc.power_ons,
        power_offs=acc.power_offs)


def _grid_balancer(specs: Sequence[SweepSpec]):
    """The batched engine's MigrationParams when any spec runs migrations."""
    if any(s.migration_enabled for s in specs):
        return RULE_BALANCER.params()
    return None


#: Default home of jax's persistent compilation cache: ``.jax_cache/`` at
#: the checkout root (``src/repro/sim/sweep.py`` -> three levels up).  It
#: is resolved from this file, not the working directory, so every process
#: started from the same checkout finds the same entries.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    Re-invoking the same grid shapes otherwise pays the full XLA compile
    in every process; with the cache warm, a re-invocation only pays trace
    + executable load.  When ``JAX_COMPILATION_CACHE_DIR`` is set, jax
    already reads that directory and nothing here overrides it; otherwise
    the cache is :data:`CHECKOUT_CACHE_DIR`.  Call it before the first
    compile: jax fixes the directory when it first consults the cache.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # Sweep programs are small but slow to build: cache everything.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


#: Per-bucket records from the most recent batched ``run_sweep`` /
#: ``run_sweep_batched`` call: shape class, cell count, mesh size, the
#: split timing -- ``compile_s`` (AOT compile wall for never-seen program
#: shapes, ~0 on a warm in-process or persistent cache), ``pack_s``
#: (host-side array packing), ``run_s`` (dispatch-to-harvest wall) -- and
#: the call's ``sweep`` id, the bucket's host ``spans`` (``{name:
#: seconds}``, ``BatchResult.spans``) and in-scan ``counters``
#: (``BatchResult.counters``).  Bucket 0's record also carries
#: ``sweep_spans``, the call-level spans (``sweep``, ``sweep.build``,
#: ``sweep.partition``, ``sweep.assemble``), and, from ``run_sweep``,
#: ``sweep_counters``: the trace banks built from segment tables and from
#: per-VM traces.  Benchmarks read it to report the cost split per bucket.
LAST_BATCH_INFO: list = []

#: Worker threads for the overlapped pipeline: bucket N+1 packs and
#: AOT-compiles while bucket N executes on the device.
_PIPELINE_WORKERS = 4


def _pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << max(int(n) - 1, 0).bit_length()


def _bucket_key(cell) -> tuple[int, int]:
    """Pow2-padded shape class of one cell: (hosts, max VMs on one host).

    Mirrors the CSR/pow2-pad approach of the segmented Pallas kernel: cells
    pack to their class bounds instead of the global grid max, so a mixed
    10/100/1000-host grid compiles a few small programs rather than padding
    every cell to 1000 hosts, and recompiles only happen on doublings.
    """
    counts: dict[str, int] = {}
    for v in cell.snapshot.vms.values():
        counts[v.host_id] = counts.get(v.host_id, 0) + 1
    return (_pow2(len(cell.snapshot.hosts)),
            _pow2(max(counts.values(), default=1)))


def _harvest_order(n: int) -> Sequence[int]:
    """Order in which the pipeline harvests its dispatched buckets (indices
    into the bucket list).  Results are keyed per cell and re-assembled in
    specs x policies order afterwards, so *any* order yields the same grid;
    tests monkeypatch this to shuffle completion and prove it."""
    return range(n)


def _cell_results(res, keys) -> dict:
    """{(spec.name, policy): SweepCellResult} for one bucket's BatchResult.

    Device wall (``run_s``, excluding compile) is attributed evenly:
    per-cell ``wall_s`` is ``run_s / n_cells``, so ``ticks_per_s`` reads as
    aggregate throughput."""
    per_cell_wall = max(res.run_s, 1e-9) / len(keys)
    out = {}
    for i, (spec, p) in enumerate(keys):
        acc = res.accumulators(i)
        out[(spec.name, p)] = SweepCellResult(
            spec=spec, policy=p, wall_s=per_cell_wall, ticks=res.ticks,
            ticks_per_s=res.ticks / per_cell_wall,
            cpu_satisfaction=acc.cpu_satisfaction(),
            cpu_payload_mhz_s=acc.cpu_payload_mhz_s,
            energy_j=acc.energy_j,
            cap_changes=acc.cap_changes,
            vmotions=acc.vmotions,
            power_ons=acc.power_ons,
            power_offs=acc.power_offs)
    return out


def _run_pipeline(buckets, sweep_id: int, sweep_spans: dict,
                  n_devices: Optional[int] = None,
                  slot_slack: float = 3.0) -> dict:
    """Overlapped execution of prepared buckets; the device never waits on
    the host.

    ``buckets`` is a list of ``(pad_hosts, pad_slots, cells, keys,
    balancer)`` work items.  A worker pool packs every bucket's arrays and
    AOT-compiles its shape class concurrently (``BatchedSimulator`` +
    ``compile()``); the main thread dispatches each bucket asynchronously
    the moment it is ready (``run_async`` -- no ``block_until_ready``
    between buckets), so while one bucket executes the next is already
    packing.  Results are harvested only at the end (in
    :func:`_harvest_order`), merged into the flat ``{(spec.name, policy):
    result}`` map, and one record per bucket lands in
    :data:`LAST_BATCH_INFO` in bucket order, bucket 0's carrying the
    call's ``sweep_spans``.
    """
    from concurrent.futures import ThreadPoolExecutor, as_completed

    from repro.sim.batch import BatchedSimulator

    enable_compilation_cache()

    def build(i):
        hp, jp, cells, _, balancer = buckets[i]
        sim = BatchedSimulator(cells, slot_slack=slot_slack,
                               balancer=balancer, n_devices=n_devices,
                               pad_hosts=hp, pad_slots=jp,
                               span_ids={"sweep": sweep_id, "bucket": i})
        sim.compile()
        return i, sim

    pendings = [None] * len(buckets)
    with ThreadPoolExecutor(
            max_workers=min(len(buckets), _PIPELINE_WORKERS)) as pool:
        futs = [pool.submit(build, i) for i in range(len(buckets))]
        for fut in as_completed(futs):
            i, sim = fut.result()
            pendings[i] = sim.run_async()
    flat: dict = {}
    infos = [None] * len(buckets)
    for i in _harvest_order(len(buckets)):
        res = pendings[i].result()
        hp, jp, cells, keys, _ = buckets[i]
        infos[i] = {
            "bucket": (hp or None, jp or None),
            "n_cells": len(cells),
            "n_devices": res.n_devices,
            "compile_s": res.compile_s,
            "pack_s": res.pack_s,
            "run_s": res.run_s,
            "sweep": sweep_id,
            "spans": res.spans,
            "counters": res.counters,
        }
        with span("sweep.assemble", sweep_spans, sweep=sweep_id):
            flat.update(_cell_results(res, keys))
    infos[0]["sweep_spans"] = sweep_spans
    LAST_BATCH_INFO.extend(infos)
    return flat


def _run_buckets(cells, keys, sweep_id: int, sweep_spans: dict,
                 n_devices: Optional[int] = None,
                 slot_slack: float = 3.0) -> dict:
    """Pad-bucket partitioner: group cells into pow2 (H, J) shape classes,
    one compiled program per bucket, each bucket's cells axis sharded over
    the device mesh, all buckets overlapped through the pipeline.  Returns
    the flat {(spec.name, policy): result} map."""
    with span("sweep.partition", sweep_spans, sweep=sweep_id):
        by_bucket: dict[tuple[int, int], list] = {}
        for c, k in zip(cells, keys):
            by_bucket.setdefault(_bucket_key(c), []).append((c, k))
        work = []
        for (hp, jp), pairs in sorted(by_bucket.items()):
            bspecs = list(dict.fromkeys(k[0] for _, k in pairs))
            work.append((hp, jp, [c for c, _ in pairs],
                         [k for _, k in pairs], _grid_balancer(bspecs)))
    return _run_pipeline(work, sweep_id, sweep_spans, n_devices=n_devices,
                         slot_slack=slot_slack)


@contextlib.contextmanager
def _gc_pause():
    """Suspend cyclic garbage collection for a bounded construction phase.

    Building a grid's cells allocates thousands of long-lived objects in
    one burst (snapshots, hosts, VM dataclasses); the allocation spike
    trips repeated full collections that rescan the entire heap -- jax's
    module graph included -- without ever finding reclaimable cycles, and
    those scans dominated end-to-end sweep wall time.  Collection resumes (if it was on) when the phase
    ends; nothing built here is cyclic garbage."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def _build_batch_cells(specs: Sequence[SweepSpec],
                       policies: Sequence[str],
                       counters: Optional[dict] = None):
    """Materialize the grid's cells, packing each spec's ``TraceBank`` once.

    Each bank is packed straight from the cluster's segment table
    (``TraceBank.from_table``): no per-VM trace object is made, and a
    cell's ``traces`` makes its callables only if something reads them.
    Policies of one spec usually share identical tables (`cpc`/`static`
    always do; `statichigh` differs only when the trace draw depends on the
    powered-on host count), so the bank is built for the first policy and
    reused wherever the tables compare equal, across policies and whatever
    pad bucket the cell later lands in.  ``counters["banks_from_table"]``
    counts the banks built.

    Construction itself is shared at two further levels, legal because
    the batched engine treats cell snapshots as read-only pack sources:
    ``policy`` only influences deployment through the ``statichigh``
    branch, so every spread-deployment policy (`cpc`/`static`) of one
    spec reuses a single build (one snapshot, one table, one bank for two
    cells), and a grid-wide ``vm_memo`` shares the ``VirtualMachine`` list
    across all cells with the same (VM count, powered-on hosts) --
    host-side scenario construction sits on the end-to-end critical path.
    """
    from repro.sim.batch import BatchCell
    cells, keys = [], []
    vm_memo: dict = {}
    with _gc_pause():
        for spec in specs:
            table = bank = None
            built: dict = {}            # deployment class -> _build_cell()
            for p in policies:
                dep = "statichigh" if p == "statichigh" else "spread"
                if dep not in built:
                    snap, t, cfg = _build_cell(spec, p, vm_memo=vm_memo)
                    built[dep] = (snap, workloads.TableTraces(t), cfg)
                snap, traces, cfg = built[dep]
                if table is None or not traces.table.same_demand(table):
                    table = traces.table
                    bank = table.bank()
                    if counters is not None:
                        counters["banks_from_table"] += 1
                cells.append(BatchCell(
                    name=f"{spec.name}/{p}", snapshot=snap, traces=traces,
                    config=cfg, powercap_enabled=(p == "cpc"),
                    dpm_enabled=spec.dpm_enabled,
                    balancer_enabled=spec.migration_enabled,
                    trace_bank=bank))
                keys.append((spec, p))
    return cells, keys


def run_sweep(specs: Sequence[SweepSpec],
              policies: Sequence[str] = POLICIES,
              engine: str = "vector",
              on_unsupported: str = "raise",
              n_devices: Optional[int] = None
              ) -> dict[str, dict[str, SweepCellResult]]:
    """Run the grid; returns results[spec.name][policy].

    ``engine="batch"`` routes the grid through the jit-compiled
    :class:`repro.sim.batch.BatchedSimulator` instead of cell-at-a-time
    Python execution.  Cells are first grouped into pow2-padded ``(hosts,
    VMs/host)`` shape classes (*pad buckets*): one compiled program per
    bucket, each sharded over the ``("cells",)`` device mesh, so a mixed
    10/100/1000-host grid neither pads every cell to the global max nor
    recompiles per unique size.  ``n_devices=None`` shards over every
    visible device; pass 1 to force single-device execution.

    A grid with cells requesting a regime the batched engine cannot replay
    exactly raises :class:`repro.sim.batch.BatchUnsupported` (the
    default); with ``on_unsupported="fallback"`` the grid is
    *partitioned* instead -- the supported cells run batched, only the
    offending cells (named in the warning) run on the sequential
    ``VectorSimulator``, and the results are merged -- never silently
    freezing the unsupported dimension.  Merged results always follow the
    input ``specs`` x ``policies`` order, whatever the partitioning.

    Each batched call takes a fresh ``sweep`` id and records its host spans
    (``repro.sim.spans``) into :data:`LAST_BATCH_INFO`, and in bucket 0's
    ``sweep_counters`` how many trace banks it built from segment tables
    (``banks_from_table``) and from per-VM traces (``banks_from_traces``,
    one per vector-engine fallback cell).
    """
    if engine == "batch":
        from repro.sim.batch import BatchedSimulator, BatchUnsupported
        LAST_BATCH_INFO.clear()
        sweep_id, record = next_sweep_id(), {}
        counters = {"banks_from_table": 0, "banks_from_traces": 0}
        with span("sweep", record, sweep=sweep_id):
            with span("sweep.build", record, sweep=sweep_id):
                cells, keys = _build_batch_cells(specs, policies, counters)
            with span("sweep.partition", record, sweep=sweep_id):
                reasons = BatchedSimulator.unsupported_cells(
                    cells, _grid_balancer(specs))
            if reasons and on_unsupported != "fallback":
                # Probe the whole grid up front: bucketing could otherwise
                # mask e.g. a time-grid mismatch by splitting the
                # disagreeing cells into different buckets.
                name, why = min(reasons.items())
                raise BatchUnsupported(f"cell {name!r}: {why}")
            if reasons:
                warnings.warn(
                    "batched engine cannot run cells "
                    f"{sorted(reasons)[:5]}"
                    f"{'...' if len(reasons) > 5 else ''} "
                    f"({next(iter(reasons.values()))}); running those on "
                    "the sequential vector engine and batching the rest",
                    RuntimeWarning, stacklevel=2)
            good = [(c, k) for c, k in zip(cells, keys)
                    if f"{k[0].name}/{k[1]}" not in reasons]
            flat = (_run_buckets([c for c, _ in good], [k for _, k in good],
                                 sweep_id, record, n_devices=n_devices)
                    if good else {})
            with span("sweep.assemble", record, sweep=sweep_id):
                out: dict[str, dict[str, SweepCellResult]] = {}
                for spec in specs:
                    out[spec.name] = {}
                    for p in policies:
                        res = flat.get((spec.name, p))
                        if res is None:
                            # The vector engine packs the cell's bank from
                            # its per-VM traces.
                            counters["banks_from_traces"] += 1
                            res = run_cell(spec, p, engine="vector")
                        out[spec.name][p] = res
        if LAST_BATCH_INFO:
            LAST_BATCH_INFO[0]["sweep_counters"] = counters
        return out
    out = {}
    for spec in specs:
        out[spec.name] = {p: run_cell(spec, p, engine=engine)
                          for p in policies}
    return out


def run_sweep_batched(specs: Sequence[SweepSpec],
                      policies: Sequence[str] = POLICIES,
                      slot_slack: float = 3.0,
                      _prebuilt=None,
                      n_devices: Optional[int] = None
                      ) -> dict[str, dict[str, SweepCellResult]]:
    """One jitted program over the whole (spec x policy) grid.

    All specs must share ``duration_s``/``tick_s``/``drs_period_s`` (true
    for :func:`scenario_families` grids); cluster size, budget, spike
    family, host mix, churn family, rule family, and policy vary per cell.
    Unlike :func:`run_sweep`'s bucketed batch path, cells pack exactly to
    the grid max ``(H, J)`` (no pow2 padding) -- the predictable shape the
    committed benchmark baselines were measured against.  The cells axis is
    still sharded over ``n_devices`` (default: all visible devices).
    """
    # ``_prebuilt`` lets callers hand over a grid they already constructed
    # instead of rebuilding every cell.
    cells, keys = _prebuilt or _build_batch_cells(specs, policies)
    LAST_BATCH_INFO.clear()
    flat = _run_pipeline([(0, 0, cells, keys, _grid_balancer(specs))],
                         next_sweep_id(), {}, n_devices=n_devices,
                         slot_slack=slot_slack)
    out: dict[str, dict[str, SweepCellResult]] = {}
    for spec, p in keys:
        out.setdefault(spec.name, {})[p] = flat[(spec.name, p)]
    return out


def scenario_families(sizes: Sequence[int] = (10, 100, 1000),
                      budgets_per_host_w: Sequence[float] = (250.0,),
                      spikes: Sequence[str] = ("burst", "prime"),
                      heterogeneous: Sequence[bool] = (False, True),
                      churns: Sequence[str] = ("none",),
                      rules: Sequence[str] = ("none",),
                      duration_s: float = 1200.0,
                      tick_s: float = 10.0) -> list[SweepSpec]:
    """The full grid: size x budget x spike x host mix x churn x rules."""
    specs = []
    for n in sizes:
        for b in budgets_per_host_w:
            for spike in spikes:
                for het in heterogeneous:
                    for churn in churns:
                        for rule in rules:
                            name = (f"h{n}_b{int(b)}w_{spike}"
                                    f"{'_het' if het else ''}"
                                    f"{'' if churn == 'none' else '_' + churn}"
                                    f"{'' if rule == 'none' else '_' + rule}")
                            specs.append(SweepSpec(
                                name=name, n_hosts=n, rack_budget_w=b * n,
                                spike=spike, heterogeneous=het, churn=churn,
                                rules=rule, duration_s=duration_s,
                                tick_s=tick_s))
    return specs


def row_contention_specs(sizes: Sequence[int] = (10, 100),
                         duration_s: float = 1200.0,
                         tick_s: float = 10.0) -> list[SweepSpec]:
    """The ``two_row`` budget-tree family: a row limit binds before the
    rack budget does (burst concentrated on row 0), in the cap-only
    management regime -- the grid where CloudPowerCap's tree-aware
    redistribution separates from Static within a row."""
    return [SweepSpec(name=f"h{n}_row_contention", n_hosts=n,
                      spike="burst", tree="two_row",
                      duration_s=duration_s, tick_s=tick_s)
            for n in sizes]


def scale_ladder(sizes: Sequence[int] = (10, 100, 1000),
                 spike: str = "burst",
                 duration_s: float = 600.0,
                 tick_s: float = 10.0) -> list[SweepSpec]:
    """The ``sweep_scale`` benchmark ladder: one spike family per size."""
    return [SweepSpec(name=f"h{n}_{spike}", n_hosts=n, spike=spike,
                      duration_s=duration_s, tick_s=tick_s)
            for n in sizes]
