"""Batched multi-cluster engine: one jitted program for a whole scenario grid.

``BatchedSimulator`` packs S scenario cells x H hosts x J VM slots per host
into padded device arrays (reusing :class:`repro.sim.workloads.TraceBank`'s
step-function layout for the demand traces) and runs the whole grid as a
single JAX program: tick delivery is a ``lax.scan`` over time, and every DRS
period the jitted manager invocation -- the same redivvy -> balance -> DPM
redistribution sequence :class:`repro.core.manager_core.ManagerCore` drives
on the object plane, built from the same ``repro.core.kernels`` -- runs for
all cells at once.  Where ``repro.sim.sweep.run_sweep`` executes the grid
cell-at-a-time through the NumPy ``VectorSimulator``, this engine executes
it grid-at-a-time -- the step that makes policy experiments grid-scale
instead of cell-scale (the ``sweep_grid`` / ``sweep_grid_dpm`` benchmark
entries).

Layout note: VMs live in a *dense slot* layout ``(S, H, J)`` -- each VM
occupies a slot under its resident host -- so every per-host reduction
(waterfill sums, delivered capacity, memory pressure) is a trailing-axis
``sum`` instead of a scatter-add: the difference between an
accelerator-friendly program and one bottlenecked on ``segment_sum``.

Two regimes, chosen at pack time:

  * **cap-only** (no cell has DPM, scripted power events, or a reason to
    migrate): placements and host power states are frozen, the
    static-schedule fast path of PR 2.
  * **dynamic** (any cell has ``dpm_enabled`` or ``config.power_events``,
    or the grid can migrate -- placement-rule violations to correct, or a
    live migration balancer): the host power-state axis and the dense slot
    assignment both become scan state.  Every DRS invocation replays the
    full object-plane sequence from the shared kernels: constraint
    correction with the injected capacity view (fundable capacity under
    CloudPowerCap, paper Fig. 3), RedivvyPowerCap, BalancePowerCap, the
    greedy migration balancer (``kernels.balance_migrations``), then the
    DPM triggers and Powercap Redistribution with rule-aware evacuation
    planning.  Migrations execute as atomic dense-slot remaps when the
    cells run the object plane's ``instant_migrations`` regime, or -- for
    gated timed cells (``SimConfig.migration_gated``) -- through a
    per-cell in-flight table carried as scan state: launches are bounded
    by per-host migration slots and a cluster bandwidth budget (deferred
    moves are simply re-scored next invocation), both endpoints burn
    vMotion overhead during the copy, and entries commit FIFO via the
    same ``move_slot`` scatter the what-if used, so the planes stay
    bit-identical (Sec. V's migration cost model).  A power-off's
    deferred cap changes apply when its timer fires, exactly as the
    action schema's prerequisite edges order them.  Scripted events (host
    failure, maintenance windows) flip the mask on schedule.  DRS
    invocations defer while power actions or migrations are in flight, so
    the schedule itself is carried per cell.

Placement rules ride along as dense slot columns (built from
``repro.drs.arrays.RulesPack``): per-VM affinity-group ids, per-rule
anti-affinity membership masks, and allowed-host bitmasks, all remapped
with their VM when it moves.

Within its regime the engine replays the exact protocol of
``Simulator.run()``; parity against ``VectorSimulator`` is enforced by
``tests/test_batch_parity.py`` and ``tests/test_migration_parity.py``
(exact cap-change / power-on / power-off / vmotion counts,
float-tolerance payload/energy).

Cells requesting anything the engine cannot replay exactly (per-VM trace
callables without a declarative spec, *ungated* timed migrations -- whose
runtime concurrency gate is data-dependent scheduling the scan cannot
precompute -- or mixed time grids / migration models) raise
:class:`BatchUnsupported` at pack time rather than silently freezing the
unsupported dimension.

The S-cells axis shards across devices (``n_devices=``): the packed
arrays split over a 1-D ``("cells",)`` mesh
(:func:`repro.launch.mesh.make_cells_mesh`) with ``shard_map``, each
device scanning its slice of cells through the identical compiled step.
Cells are embarrassingly parallel, so no collective crosses the cells
axis inside the scan -- sharding is a pure reshape of the work and
per-cell results stay bit-identical to the single-device run
(``tests/test_sharded_parity.py``).  When S doesn't divide the mesh the
cells axis is padded with duplicates of the leading cells and outputs
sliced back.  ``pad_hosts``/``pad_slots`` let ``run_sweep``'s pad-bucket
partitioner compile one program per pow2 ``(H, J)`` shape class instead
of one per unique grid shape.

Everything runs in float64 (``jax.enable_x64(True)``) so the compiled
program tracks the NumPy object plane to reduction-order rounding.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import threading
import time
import warnings
from typing import Mapping, NamedTuple, Optional, Sequence

import numpy as np

from repro import backend as backend_mod
from repro.backend import jax_backend
from repro.core import kernels
from repro.drs import rules as rules_mod
from repro.drs.arrays import RulesPack, dense_slot_assignment
from repro.drs.entitlement import waterfill_dense
from repro.drs.snapshot import ClusterSnapshot
from repro.sim.cluster import SimConfig
from repro.sim.metrics import Accumulators, fold_timeseries
from repro.sim.spans import span
from repro.sim.workloads import DemandTrace, TraceBank


class BatchUnsupported(ValueError):
    """A cell requests a regime the batched engine cannot replay exactly."""


@dataclasses.dataclass
class BatchCell:
    """One scenario cell: a cluster, its demand traces, and its policy."""

    name: str
    snapshot: ClusterSnapshot
    traces: Mapping[str, DemandTrace]
    config: SimConfig
    powercap_enabled: bool = True            # False => Static/StaticHigh
    window: Optional[tuple[float, float]] = None
    dpm_enabled: bool = False                # phase-3 DPM + redistribution
    # Whether the hill-climb migration balancer runs for this cell (the
    # simulator-level twin of the manager's ``BalancerConfig.max_moves``
    # being nonzero); only meaningful when the batch is built with a
    # ``balancer`` whose ``max_moves > 0``.
    balancer_enabled: bool = True
    # Optional pre-packed ``TraceBank`` over ``list(snapshot.vms)`` (the
    # order ``dense_slot_assignment`` enumerates).  The sweep layer packs
    # each spec's traces once and shares the bank across the policies and
    # pad buckets that reuse them -- host-side packing dominated the
    # end-to-end sweep wall before this.  ``None`` packs from ``traces``.
    trace_bank: Optional[TraceBank] = None


class _StaticSpec(NamedTuple):
    """Hashable compile key: everything that shapes the jitted program."""

    n_cells: int
    n_hosts: int
    n_slots: int
    n_tags: int
    n_events: int
    tick_s: float
    waterfill_iters: int
    balance: kernels.BalanceParams
    churn: bool
    dpm: kernels.DPMParams
    drs_period_s: float
    drs_first_at_s: float
    power_on_latency_s: float
    power_off_latency_s: float
    migration: bool = False                  # correction/balancer live
    rules: kernels.RulesMeta = kernels.RulesMeta()
    balancer: kernels.MigrationParams = kernels.MigrationParams(max_moves=0)
    # Timed-vMotion regime: migrations live in a per-cell in-flight table
    # carried as scan state (``mig_table`` rows), launches are gated by
    # ``limits`` (the batch twin of ``SimConfig.migration_gated``), and
    # both endpoints burn ``vmotion_overhead_mhz`` until the copy at
    # ``vmotion_rate_mb_s`` commits.  ``limits`` also applies to gated
    # *instant* grids (launch bounding without the copy window).
    timed: bool = False
    mig_table: int = 1
    limits: kernels.MigrationLimits = kernels.MigrationLimits()
    vmotion_rate_mb_s: float = 128.0
    vmotion_overhead_mhz: float = 1500.0
    # Allocation-kernel executor captured at pack time ("jax" or
    # "jax-pallas"): part of the compile key, and re-pinned around the
    # program run so trace-time dispatch cannot drift if the process-wide
    # executor changes between pack() and the first run().
    executor: str = "jax"
    # Emit the full per-tick metric series as scan outputs instead of only
    # the reduced in-carry summaries.  The default (False) transfers just
    # the ``(S,)`` reductions off device; parity tests flip this on and
    # check the carry fold against ``fold_timeseries`` bit for bit.
    keep_timeseries: bool = False
    # Budget-tree node axis: 0 compiles the flat scalar-budget program
    # (byte-identical to pre-tree builds); > 0 packs per-cell ancestor
    # incidence / limit / depth columns and threads the tree through every
    # cap-producing kernel (projection after redivvy and balance, scoped
    # funding/reabsorption/evacuation) plus an ``over_tree`` invariant
    # carried through the scan.  Cells without a tree ride along as a
    # single root node limited at their scalar budget (a bitwise no-op).
    n_tree_nodes: int = 0


@dataclasses.dataclass
class BatchResult:
    """Per-cell accumulators, as arrays over the S cells."""

    names: list
    cpu_payload_mhz_s: np.ndarray
    cpu_demand_mhz_s: np.ndarray
    mem_payload_mb_s: np.ndarray
    mem_demand_mb_s: np.ndarray
    energy_j: np.ndarray
    cap_changes: np.ndarray                  # int per cell
    vmotions: np.ndarray                     # int per cell (DPM evacuations)
    power_ons: np.ndarray                    # int per cell
    power_offs: np.ndarray                   # int per cell
    tag_names: list
    tag_payload: np.ndarray                  # (S, G)
    tag_demand: np.ndarray                   # (S, G)
    window_fields: dict                      # field -> (S,) array
    has_window: np.ndarray                   # bool per cell
    final_caps: np.ndarray                   # (S, H)
    final_on: np.ndarray                     # (S, H) power states at the end
    final_occ: np.ndarray                    # (S, H, J) final slot occupancy
    ticks: int
    n_devices: int = 1                       # cells-mesh size the run used
    # Timing split: AOT compile wall for this batch's program shape (0.0 on
    # a warm in-process cache), host-side packing wall from ``_pack``, and
    # dispatch-to-harvest wall (dispatch, device wait, conversions).
    compile_s: float = 0.0
    pack_s: float = 0.0
    run_s: float = 0.0
    # The run's host spans, ``{name: seconds}`` (``repro.sim.spans``):
    # ``batch.pack`` and ``batch.compile`` (AOT miss only) when this was
    # the simulator's first dispatch, then ``batch.dispatch``,
    # ``batch.wait``, ``batch.fetch`` and ``batch.check``.
    spans: dict = dataclasses.field(default_factory=dict)
    # In-scan counters: ``balance_trips``, BalancePowerCap loop trips summed
    # over the scan's manager invocations (the loop runs until every cell
    # of a device's shard is done; the largest over shards), and
    # ``drs_invocations``, the DRS schedule's invocations (``_drs_schedule``;
    # in the churn regime a deferred invocation can add calls).  The churn
    # regime adds ``evac_trips``, the DPM evacuation planner's loop trips
    # summed over the scan's manager invocations (largest over shards), and
    # ``power_offs`` and ``power_ons``, summed over cells.
    counters: dict = dataclasses.field(default_factory=dict)
    # ``keep_timeseries=True`` only: field -> (T, S) per-tick rates (floats)
    # and per-tick action counts (ints); ``None`` on the reduced path.
    timeseries: Optional[dict] = None
    tick_s: float = 0.0                      # dt the timeseries folds with

    def reduced_timeseries(self) -> dict:
        """Fold :attr:`timeseries` into run summaries via the carry's exact
        arithmetic (see :func:`repro.sim.metrics.fold_timeseries`)."""
        if self.timeseries is None:
            raise ValueError("run with keep_timeseries=True first")
        return fold_timeseries(self.timeseries, self.tick_s)

    def accumulators(self, i: int) -> Accumulators:
        acc = Accumulators(
            cpu_payload_mhz_s=float(self.cpu_payload_mhz_s[i]),
            cpu_demand_mhz_s=float(self.cpu_demand_mhz_s[i]),
            mem_payload_mb_s=float(self.mem_payload_mb_s[i]),
            mem_demand_mb_s=float(self.mem_demand_mb_s[i]),
            energy_j=float(self.energy_j[i]),
            cap_changes=int(self.cap_changes[i]),
            vmotions=int(self.vmotions[i]),
            power_ons=int(self.power_ons[i]),
            power_offs=int(self.power_offs[i]))
        for g, tag in enumerate(self.tag_names):
            if self.tag_demand[i, g] > 0.0 or self.tag_payload[i, g] > 0.0:
                acc.tag_payload[tag] = float(self.tag_payload[i, g])
                acc.tag_demand[tag] = float(self.tag_demand[i, g])
        return acc

    def window_accumulators(self, i: int) -> Optional[Accumulators]:
        if not bool(self.has_window[i]):
            return None
        w = self.window_fields
        return Accumulators(
            cpu_payload_mhz_s=float(w["cpu_payload_mhz_s"][i]),
            cpu_demand_mhz_s=float(w["cpu_demand_mhz_s"][i]),
            mem_payload_mb_s=float(w["mem_payload_mb_s"][i]),
            mem_demand_mb_s=float(w["mem_demand_mb_s"][i]),
            energy_j=float(w["energy_j"][i]))


def _drs_schedule(cfg: SimConfig) -> tuple[np.ndarray, np.ndarray]:
    """Tick times and manager-invocation mask, mirroring ``Simulator.run()``
    (cap changes are instantaneous, so no invocation is ever deferred)."""
    ts, fire = [], []
    next_drs = cfg.drs_first_at_s
    t = 0.0
    while t < cfg.duration_s:
        hit = t >= next_drs
        if hit:
            next_drs = t + cfg.drs_period_s
        ts.append(t)
        fire.append(hit)
        t += cfg.tick_s
    return np.asarray(ts, dtype=np.float64), np.asarray(fire, dtype=bool)


# Padding values restored to a slot when its VM migrates to another host
# (extends the kernel layer's pads with the trace/tag columns; "bps" needs
# an array pattern and is added per-program).
_SLOT_PAD = dict(kernels.SLOT_PAD, period=np.inf, cpu_vals=0.0,
                 mem_vals=0.0, tag_masks=False, vm=-1)


def trace_demands(tr, t):
    """(cpu, mem) of every slot's step-function demand trace at time ``t``.

    ``tr`` holds the packed trace columns in ``TraceBank``'s layout:
    ``period`` (...,) and ``bps``, ``cpu_vals``, ``mem_vals`` (..., K).
    The segment index is ``TraceBank.eval``'s; the value is selected by
    ``idx == k`` over the static, short segment axis.  XLA lowers a
    ``take_along_axis`` there to a serial per-element gather, which took
    nine tenths of the cap-only scan's time on a TPU v5e; the select
    chain is elementwise and returns the gather's values bit for bit.
    """
    import jax
    import jax.numpy as jnp

    with jax.named_scope("repro/demand"):
        phase = jnp.where(jnp.isfinite(tr["period"]),
                          jnp.mod(t, tr["period"]), t)
        idx = jnp.clip(
            jnp.sum(tr["bps"] <= phase[..., None], axis=-1) - 1, 0, None)
        cpu = tr["cpu_vals"][..., 0]
        mem = tr["mem_vals"][..., 0]
        for k in range(1, tr["bps"].shape[-1]):
            hit = idx == k
            cpu = jnp.where(hit, tr["cpu_vals"][..., k], cpu)
            mem = jnp.where(hit, tr["mem_vals"][..., k], mem)
    return cpu, mem


def _build_program(static: _StaticSpec):
    """Build the (untraced) whole-grid program for one per-device shape."""
    import jax
    import jax.numpy as jnp

    be = jax_backend()
    S, H, J = static.n_cells, static.n_hosts, static.n_slots
    dt = static.tick_s
    wf_iters = static.waterfill_iters
    dpmp = static.dpm
    h_idx = np.arange(H)
    s_idx = np.arange(S)

    FIELDS = ("cpu_payload_mhz_s", "cpu_demand_mhz_s",
              "mem_payload_mb_s", "mem_demand_mb_s", "energy_j")

    def scope(name):
        """Named scope of one manager phase: every op traced inside carries
        ``repro/<name>`` in its HLO metadata (``op_name``), which a device
        profile groups by; the innermost ``repro/`` scope names the phase.
        Metadata only: it changes nothing XLA fuses."""
        return jax.named_scope(f"repro/{name}")

    def make_demands(a):
        def demands(t, trace=None):
            return trace_demands(a if trace is None else trace, t)
        return demands

    def make_deliver(a):
        def deliver(hosts, caps, on, active, weights, reservation, limit,
                    tag_masks, cpu, mem, overhead=None):
            host_mem = jnp.where(on, a["host_mem"], 0.0)
            managed = kernels.managed_capacity(jnp, hosts, caps)
            if overhead is not None:
                # In-flight vMotions burn endpoint CPU: delivery capacity
                # shrinks, and the burned cycles still count toward Eq. 1
                # utilization below (they never exceed managed capacity,
                # so the object plane's clip at 1.0 stays a no-op).
                managed = jnp.maximum(managed - overhead, 0.0)
            dem = jnp.where(active, jnp.minimum(cpu, limit), 0.0)
            floors = jnp.where(active, jnp.minimum(reservation, dem), 0.0)
            alloc = waterfill_dense(jnp, be.fori, managed, floors, dem,
                                    weights, wf_iters, active=active)
            delivered_h = jnp.sum(alloc, axis=-1)
            mem_d = jnp.where(active, mem, 0.0)
            mem_dem_h = jnp.sum(mem_d, axis=-1)
            mem_deliv = jnp.minimum(mem_dem_h, host_mem)
            # Eq. 1 power, utilization measured against peak capacity.
            util = delivered_h / a["cap_peak"]
            if overhead is not None:
                util = (delivered_h + overhead) / a["cap_peak"]
            power = kernels.power_consumed(jnp, hosts, util)
            tick = {
                "cpu_payload_mhz_s": jnp.sum(alloc, axis=(-1, -2)),
                "cpu_demand_mhz_s": jnp.sum(dem, axis=(-1, -2)),
                "mem_payload_mb_s": jnp.sum(mem_deliv, axis=-1),
                "mem_demand_mb_s": jnp.sum(mem_dem_h, axis=-1),
                "energy_j": jnp.sum(power * on, axis=-1),
            }
            # tag_masks: (S, H, J, G)
            tag_pay = jnp.sum(tag_masks * alloc[..., None], axis=(-3, -2))
            tag_dem = jnp.sum(tag_masks * dem[..., None], axis=(-3, -2))
            return tick, tag_pay, tag_dem, mem_dem_h
        return deliver

    # ------------------------------------------------------------------
    def build_static(a):
        """Cap-only regime: frozen placements and power states (PR 2)."""
        hosts = kernels.HostCols(a["on"], a["idle"], a["peak"],
                                 a["cap_peak"], a["hyp"])
        on = a["on"]
        active = a["occ"] & on[..., None]
        weights = a["weights"]
        floor_caps = kernels.reserved_floor_caps(jnp, hosts, a["cpu_res"])
        vm_floors = jnp.where(active,
                              jnp.minimum(a["reservation"], a["limit"]), 0.0)
        demands = make_demands(a)
        deliver = make_deliver(a)
        tcols = None
        if static.n_tree_nodes:
            tcols = kernels.TreeCols(a["tree_anc"], a["tree_limit"],
                                     a["tree_depth"])

        def invoke_manager(caps, cpu):
            """Phase 1 (reserved-floor redivvy) + phase 2 (BalancePowerCap),
            counting cap changes exactly as ``order_cap_changes`` emits;
            also returns the BalancePowerCap loop's trip count."""
            with scope("manager/redivvy"):
                redivvied = kernels.redivvy_caps(jnp, on, caps, floor_caps)
                if tcols is not None:
                    # Tree projection inside the CPC branch only, exactly
                    # where the object plane's ``redivvy_power_cap``
                    # applies it.
                    with scope("manager/tree"):
                        redivvied = kernels.tree_project_caps(
                            jnp, tcols, on, redivvied, floor_caps)
                caps1 = jnp.where(a["enabled"][:, None], redivvied, caps)
                changes = kernels.count_cap_changes(jnp, on, caps, caps1)
            with scope("manager/balance"):
                vm_ceils = jnp.where(
                    active, jnp.clip(cpu, a["reservation"], a["limit"]),
                    0.0)

                def ents_at(c):
                    managed = kernels.managed_capacity(jnp, hosts, c)
                    alloc = waterfill_dense(jnp, be.fori, managed,
                                            vm_floors, vm_ceils, weights,
                                            wf_iters, active=active)
                    return jnp.sum(alloc, axis=-1)

                caps2, _, trips = kernels.balance_caps(
                    be, hosts, caps1, ents_at, a["cpu_res"], a["budget"],
                    a["enabled"], static.balance,
                    dense=kernels.DenseCols(vm_floors, vm_ceils, weights,
                                            active, wf_iters))
                if tcols is not None:
                    with scope("manager/tree"):
                        caps2 = jnp.where(
                            a["enabled"][:, None],
                            kernels.tree_project_caps(jnp, tcols, on, caps2,
                                                      floor_caps),
                            caps2)
                changes = changes + kernels.count_cap_changes(jnp, on, caps1,
                                                              caps2)
            return (caps2, changes.astype(jnp.int32),
                    jnp.asarray(trips, jnp.int32))

        def step(carry, x):
            if tcols is None:
                (caps, acc, win, tag_pay, tag_dem, n_changes,
                 max_total, trips) = carry
            else:
                (caps, acc, win, tag_pay, tag_dem, n_changes, max_total,
                 trips, over_tree) = carry
            t, is_drs, in_win = x
            cpu, mem = demands(t)
            caps, changes, n_trips = jax.lax.cond(
                is_drs,
                lambda c: invoke_manager(c, cpu),
                lambda c: (c, jnp.zeros(S, dtype=jnp.int32), jnp.int32(0)),
                caps)
            with scope("deliver"):
                tick, tp, td, _ = deliver(hosts, caps, on, active, weights,
                                          a["reservation"], a["limit"],
                                          a["tag_masks"], cpu, mem)
                acc = {k: acc[k] + tick[k] * dt for k in acc}
                win = {k: win[k] + jnp.where(in_win, tick[k], 0.0) * dt
                       for k in win}
                carry = (caps, acc, win, tag_pay + tp * dt,
                         tag_dem + td * dt, n_changes + changes,
                         jnp.maximum(max_total, jnp.sum(caps * on, axis=-1)),
                         trips + n_trips)
                if tcols is not None:
                    carry = carry + (jnp.maximum(
                        over_tree,
                        jnp.max(kernels.tree_node_sums(jnp, tcols, on, caps)
                                - tcols.limit, axis=-1)),)
            if not static.keep_timeseries:
                return carry, None
            zc = jnp.zeros(S, dtype=jnp.int32)
            return carry, dict(tick, cap_changes=changes, vmotions=zc,
                               power_ons=zc, power_offs=zc)

        zeros = {k: jnp.zeros(S) for k in FIELDS}
        init = (a["caps0"], dict(zeros), dict(zeros),
                jnp.zeros((S, static.n_tags)), jnp.zeros((S, static.n_tags)),
                jnp.zeros(S, dtype=jnp.int32),
                jnp.sum(a["caps0"] * a["on"], axis=-1), jnp.int32(0))
        if tcols is not None:
            init = init + (jnp.full(S, -jnp.inf),)
        xs = (a["ts"], a["drs_mask"], a["win_mask"])
        final, ys = jax.lax.scan(step, init, xs)
        (caps, acc, win, tag_pay, tag_dem, n_changes, max_total,
         trips) = final[:8]
        zi = jnp.zeros(S, dtype=jnp.int32)
        out = {"acc": acc, "win": win, "tag_payload": tag_pay,
               "tag_demand": tag_dem, "cap_changes": n_changes,
               "vmotions": zi, "power_ons": zi, "power_offs": zi,
               "max_total_cap": max_total, "over_budget": max_total * 0.0,
               "final_caps": caps, "final_on": a["on"],
               "final_occ": a["occ"],
               "slot_pressure": jnp.zeros(S, dtype=bool),
               "balance_trips": trips[None]}
        if tcols is not None:
            out["over_tree"] = final[8]
        if static.keep_timeseries:
            out["timeseries"] = ys
        return out

    # ------------------------------------------------------------------
    def build_churn(a):
        """Capacity-churn regime: the power-state axis is scan state."""
        demands = make_demands(a)
        deliver = make_deliver(a)
        exists = a["exists"]
        host_mem_spec = a["host_mem"]
        tcols = None
        if static.n_tree_nodes:
            tcols = kernels.TreeCols(a["tree_anc"], a["tree_limit"],
                                     a["tree_depth"])

        rule_keys = tuple(k for k in ("aff_group", "allowed", "anti")
                          if k in a)
        slot_keys = ("occ", "reservation", "limit", "weights",
                     "migratable", "period", "bps", "cpu_vals", "mem_vals",
                     "tag_masks", "vm") + rule_keys
        pads = dict(_SLOT_PAD, bps=jnp.where(
            jnp.arange(a["bps"].shape[-1]) == 0, 0.0, jnp.inf))
        M = static.mig_table                 # in-flight table rows (timed)

        def hosts_of(on):
            return kernels.HostCols(on, a["idle"], a["peak"], a["cap_peak"],
                                    a["hyp"])

        def gather_host(col, idx):
            return jnp.take_along_axis(col, idx[..., None], axis=-1)[..., 0]

        def host_sum_vm_order(vals, act, vm):
            # Per-host sum with addends in ascending global-VM-index order,
            # matching the object plane's ``np.bincount`` reduction bit for
            # bit.  A plain slot-axis ``sum`` adds in slot order, which
            # stops agreeing once a migration lands in a first-free slot;
            # on near-ties (BalancePowerCap equalizes utilizations by
            # construction) the one-ULP difference flips argmin-style
            # decisions like the DPM evacuation victim.  Sorting each host
            # row by VM index (empty slots last) and accumulating
            # left-to-right restores the exact add order; the trailing
            # +0.0 terms cannot perturb a non-negative partial sum.
            key = jnp.where(act, vm, jnp.iinfo(jnp.int64).max)
            ordr = jnp.argsort(key, axis=-1)
            sv = jnp.take_along_axis(jnp.where(act, vals, 0.0), ordr,
                                     axis=-1)
            return be.fori(sv.shape[-1], lambda j, acc: acc + sv[..., j],
                           jnp.zeros(sv.shape[:-1]))

        # ---------------------------------------------------- invocation
        def invocation(c, can, t):
            # Demands at t in the pre-invocation slot layout; they ride in
            # the working bundle so migrations move them with their VM
            # (delivery re-evaluates from the post-move slots).
            cpu, mem = demands(t, trace=c["slots"])
            mem_pre = mem                  # pre-invocation layout, for the
            on = c["on"]                   # timed duration replay below
            hosts = hosts_of(on)
            caps = c["caps"]
            work = dict(c["slots"], cpu=cpu, mem=mem)
            vmot = jnp.zeros(S, dtype=jnp.int32)
            mig_pressure = jnp.zeros(S, dtype=bool)
            # Per-invocation launch ledger, shared by correction and the
            # balancer (the batch twin of ``LaunchBudget``); the kernels
            # seed it with zeros on first use when gating is live.
            launch = None
            corr_moves = bal_moves = None
            n_corr = n_bal = None

            # Phase 1a: constraint correction under the injected capacity
            # view -- fundable capacity (reserved-floor caps plus the whole
            # unreserved pool, paper Fig. 3) for CloudPowerCap cells,
            # managed capacity at the current caps for static policies.
            if static.migration and static.rules.any:
                with scope("migration/correct"):
                    act0 = work["occ"] & on[..., None]
                    res_pre = jnp.sum(
                        jnp.where(act0, work["reservation"], 0.0), axis=-1)
                    floors_pre = kernels.reserved_floor_caps(jnp, hosts,
                                                             res_pre)
                    spare = jnp.maximum(
                        a["budget"] - jnp.sum(
                            jnp.where(on, floors_pre, 0.0), axis=-1), 0.0)
                    fundable = kernels.managed_capacity(
                        jnp, hosts,
                        jnp.minimum(floors_pre + spare[:, None], a["peak"]))
                    cap_view = jnp.where(
                        a["enabled"][:, None], fundable,
                        kernels.managed_capacity(jnp, hosts, caps))
                    cap_view = jnp.where(on, cap_view, 0.0)
                    work, corr_moves, n_corr, prs, launch = \
                        kernels.correct_constraints_slots(
                            be, hosts, cap_view, work, host_mem_spec,
                            static.rules, can,
                            jnp.full((S, max(static.rules.move_bound, 1), 3),
                                     -1, dtype=jnp.int64),
                            jnp.zeros(S, dtype=jnp.int64), pads=pads,
                            limits=static.limits, launch=launch)
                    vmot = vmot + n_corr.astype(jnp.int32)
                    mig_pressure = mig_pressure | prs

            # Phase 1b: reserved-floor redivvy (Powercap Allocation) on
            # the post-correction placements.
            with scope("manager/redivvy"):
                act3 = work["occ"] & on[..., None]
                res = work["reservation"]
                lim = work["limit"]
                cpu_res = jnp.sum(jnp.where(act3, res, 0.0), axis=-1)
                apply_cpc = can & a["enabled"]
                floor_caps = kernels.reserved_floor_caps(jnp, hosts, cpu_res)
                redivvied = kernels.redivvy_caps(jnp, on, caps, floor_caps)
                if tcols is not None:
                    with scope("manager/tree"):
                        redivvied = kernels.tree_project_caps(
                            jnp, tcols, on, redivvied, floor_caps)
                caps1 = jnp.where(apply_cpc[:, None], redivvied, caps)
                changes = jnp.where(
                    can, kernels.count_cap_changes(jnp, on, caps, caps1), 0)

            # Phase 2: BalancePowerCap.
            with scope("manager/balance"):
                vm_floors = jnp.where(act3, jnp.minimum(res, lim), 0.0)
                vm_ceils = jnp.where(act3, jnp.clip(work["cpu"], res, lim),
                                     0.0)

                def ents_at(cc):
                    managed = kernels.managed_capacity(jnp, hosts, cc)
                    alloc = waterfill_dense(jnp, be.fori, managed, vm_floors,
                                            vm_ceils, work["weights"],
                                            wf_iters, active=act3)
                    return jnp.sum(alloc, axis=-1)

                caps2, _, trips = kernels.balance_caps(
                    be, hosts, caps1, ents_at, cpu_res, a["budget"],
                    apply_cpc, static.balance,
                    dense=kernels.DenseCols(vm_floors, vm_ceils,
                                            work["weights"], act3, wf_iters))
                if tcols is not None:
                    with scope("manager/tree"):
                        caps2 = jnp.where(
                            apply_cpc[:, None],
                            kernels.tree_project_caps(jnp, tcols, on, caps2,
                                                      floor_caps),
                            caps2)
                changes = changes + jnp.where(
                    can, kernels.count_cap_changes(jnp, on, caps1, caps2), 0)

            # Phase 2b: residual imbalance fixed by actual migrations
            # (DRS's hill-climb; runs for every policy, like the object
            # plane's ManagerCore).
            if static.migration and static.balancer.max_moves > 0:
                with scope("migration/balance"):
                    work, bal_moves, n_bal, prs, launch = \
                        kernels.balance_migrations(
                            be, hosts, caps2, work, host_mem_spec,
                            static.balancer, static.rules, can & a["bal_on"],
                            jnp.full((S, static.balancer.max_moves, 3), -1,
                                     dtype=jnp.int64),
                            jnp.zeros(S, dtype=jnp.int64), pads=pads,
                            iters=kernels.MIGRATION_WATERFILL_ITERS,
                            limits=static.limits, launch=launch)
                    vmot = vmot + n_bal.astype(jnp.int32)
                    mig_pressure = mig_pressure | prs
                    act3 = work["occ"] & on[..., None]
                    res = work["reservation"]
                    lim = work["limit"]
                    cpu_res = jnp.sum(jnp.where(act3, res, 0.0), axis=-1)

            # Phase 3: DPM triggers + Powercap Redistribution, on the
            # post-migration layout.
            with scope("dpm/trigger"):
                occ = work["occ"]
                cpu = work["cpu"]
                mem = work["mem"]
                eff_slot = jnp.where(act3, jnp.clip(cpu, res, lim), 0.0)
                eff_h = host_sum_vm_order(eff_slot, act3, work["vm"])
                mem_h = host_sum_vm_order(mem, act3, work["vm"])
                cpu_util, mem_util = kernels.host_utilizations(
                    jnp, hosts, caps2, eff_h, mem_h, host_mem_spec)
                hot_any = jnp.any(kernels.dpm_hot_mask(
                    jnp, on, cpu_util, mem_util, dpmp.high_util), axis=-1)
                standby = exists & ~on
                cand = jnp.argmax(standby, axis=-1)
                do_dpm = can & a["dpm"]

            # Power-on: fund the first standby host's cap (decreases execute
            # now; the candidate's cap applies now too -- it only counts
            # toward the budget while pending -- and the host joins when the
            # power-on timer fires).
            with scope("dpm/funding"):
                want_on = do_dpm & hot_any & jnp.any(standby, axis=-1)
                funded, granted = kernels.power_on_funding_caps(
                    be, hosts, caps2, cand, cpu_util, eff_h, cpu_res,
                    a["budget"], dpmp.high_util, tree=tcols)
                cand_cols = kernels.HostCols(
                    *(gather_host(col, cand)[..., None]
                      for col in (jnp.ones_like(on), a["idle"], a["peak"],
                                  a["cap_peak"], a["hyp"])))
                feasible = kernels.managed_capacity(
                    jnp, cand_cols, granted[..., None])[..., 0] > 0.0
                do_on = want_on & jnp.where(a["enabled"], feasible, True)
                fund = do_on & a["enabled"]
                is_cand = h_idx[None, :] == cand[..., None]
                caps3 = jnp.where(fund[:, None], funded, caps2)
                changes = changes + jnp.where(
                    fund,
                    kernels.count_cap_changes(jnp, on | is_cand, caps2,
                                              funded),
                    0)
                pon_idx = jnp.where(do_on, cand, c["pon_idx"])
                pon_end = jnp.where(do_on, t + static.power_on_latency_s,
                                    c["pon_end"])

            # Power-off: sustained cluster-wide low utilization, stability
            # window elapsed, and a complete evacuation plan.
            with scope("dpm/trigger"):
                n_on = jnp.sum(on, axis=-1)
                all_low = kernels.dpm_all_low(jnp, on, cpu_util, mem_util,
                                              dpmp.low_util)
                ls = jnp.where(jnp.isnan(c["low_since"]), t, c["low_since"])
                oldest = jnp.maximum(
                    jnp.max(jnp.where(on, ls, -jnp.inf), axis=-1),
                    c["last_cfg"])
                window_ok = (t - oldest) >= dpmp.stable_window_s
                maybe_off = (do_dpm & ~hot_any & (n_on > 1) & all_low
                             & window_ok)
                victim = jnp.argmin(jnp.where(on, cpu_util, jnp.inf),
                                    axis=-1)
            with scope("dpm/evacuation"):
                evac_scope = None
                if tcols is not None:
                    evac_scope = kernels.tree_evac_scope(jnp, tcols, on,
                                                         caps2, victim)
                (ok, order, dests, n_evac, pressure,
                 evac_trips) = kernels.plan_evacuation(
                    be, hosts, caps2, victim, occ, work["vm"], eff_slot, mem,
                    res, work["migratable"], host_mem_spec,
                    dpmp.target_util, allowed=work.get("allowed"),
                    anti=work.get("anti"), scope=evac_scope)
                do_off = maybe_off & ok
                work = _apply_remap(work, do_off, victim, order, dests)
                vmot = vmot + jnp.where(do_off, n_evac, 0).astype(jnp.int32)

            with scope("dpm/reabsorb"):
                reabsorbed = kernels.power_off_reabsorb_caps(
                    jnp, hosts, caps2, victim, a["budget"], tree=tcols)
                # The deferred actions touch exactly the hosts whose cap
                # change clears the emission threshold (order_cap_changes).
                changed = on & (jnp.abs(reabsorbed - caps2)
                                > kernels.CAP_CHANGE_EPS)
                off_cpc = do_off & a["enabled"]
                pend_caps = jnp.where(
                    do_off[:, None],
                    jnp.where(off_cpc[:, None], reabsorbed, caps3),
                    c["pend_caps"])
                pend_mask = jnp.where(do_off[:, None],
                                      off_cpc[:, None] & changed,
                                      c["pend_mask"])
                pend_cnt = jnp.where(off_cpc, jnp.sum(changed, axis=-1),
                                     0).astype(jnp.int32)
                pend_cnt = jnp.where(do_off, pend_cnt, c["pend_cnt"])
                poff_idx = jnp.where(do_off, victim, c["poff_idx"])
            if static.timed:
                # ---- Timed regime: the what-if layout above only shaped
                # *decisions*.  The carry keeps the pre-invocation slots;
                # every emitted move is appended to the in-flight table and
                # commits against the live layout on its vMotion schedule
                # (step phase 2b), replaying the identical ``move_slot``
                # sequence -- first-free placement makes the trajectories
                # coincide, so the planes stay bit-identical.
                #
                # Durations replay the move sequence on a scratch
                # ``(occ, mem)`` copy so chained moves read the memory
                # footprint that travelled with their VM; each entry's
                # stored end is the running max so far (FIFO: a migration
                # cannot complete before those emitted ahead of it, the
                # object plane's ``_complete_actions`` drain).  ``idx``
                # tracks which entry last touched a slot so chained
                # launches record their predecessor: the endpoint-overhead
                # charge follows the VM's *current* host while earlier
                # chain legs are still in flight (``vm.host_id`` in the
                # object plane).
                with scope("vmotion/launch"):
                    k_idx = jnp.arange(M)
                    scratch = {"occ": c["slots"]["occ"], "mem": mem_pre,
                               "idx": jnp.full((S, H, J), -1, dtype=jnp.int64)}
                    spads = {"occ": False, "mem": 0.0, "idx": -1}
                    tb = (scratch, c["mig_src"], c["mig_j"], c["mig_dst"],
                          c["mig_end"], c["mig_prev"],
                          jnp.zeros(S, dtype=jnp.int64),     # append cursor
                          jnp.full(S, -jnp.inf))             # FIFO running max

                    def replay(n_k, take, tb):
                        def body(k, tb):
                            (sc, msrc, mj, mdst, mend, mprev, cur, eff) = tb
                            do, src, j, dst = take(k)
                            si = jnp.clip(src, 0, H - 1)
                            ji = jnp.clip(j, 0, J - 1)
                            mem_v = sc["mem"][s_idx, si, ji]
                            prev_v = sc["idx"][s_idx, si, ji]
                            dur = jnp.maximum(
                                jnp.maximum(mem_v, 64.0)
                                / static.vmotion_rate_mb_s, dt)
                            eff = jnp.where(do, jnp.maximum(eff, t + dur), eff)
                            at = do[:, None] & (k_idx[None, :] == cur[:, None])
                            msrc = jnp.where(at, src[:, None], msrc)
                            mj = jnp.where(at, j[:, None], mj)
                            mdst = jnp.where(at, dst[:, None], mdst)
                            mend = jnp.where(at, eff[:, None], mend)
                            mprev = jnp.where(at, prev_v[:, None], mprev)
                            sc = dict(sc, idx=sc["idx"].at[s_idx, si, ji].set(
                                jnp.where(do, cur, prev_v)))
                            sc, _ = kernels.move_slot(jnp, sc, do, src, j, dst,
                                                      spads)
                            cur = cur + do.astype(cur.dtype)
                            return (sc, msrc, mj, mdst, mend, mprev, cur, eff)
                        return be.fori(n_k, body, tb)

                    if corr_moves is not None:
                        tb = replay(corr_moves.shape[1], lambda k: (
                            k < n_corr, corr_moves[:, k, 0],
                            corr_moves[:, k, 1], corr_moves[:, k, 2]), tb)
                    if bal_moves is not None:
                        tb = replay(bal_moves.shape[1], lambda k: (
                            k < n_bal, bal_moves[:, k, 0],
                            bal_moves[:, k, 1], bal_moves[:, k, 2]), tb)
                    tb = replay(J, lambda k: (
                        do_off & (dests[:, k] >= 0), victim, order[:, k],
                        dests[:, k]), tb)
                    _, mig_src, mig_j, mig_dst, mig_end, mig_prev, _, _ = tb

                    # A power-off waits for its evacuation entries to commit
                    # (its prerequisite edges); evacuations are appended last
                    # and ends are FIFO-monotone, so "last evacuation done"
                    # is exactly "table drained".  No evacuees => the timer
                    # starts now, even with manager moves still in flight.
                    wait = do_off & (n_evac > 0)
                    poff_end = jnp.where(do_off & ~wait,
                                         t + static.power_off_latency_s,
                                         c["poff_end"])
                    poff_wait = jnp.where(do_off, wait, c["poff_wait"])
            else:
                poff_end = jnp.where(do_off, t + static.power_off_latency_s,
                                     c["poff_end"])

            c = dict(c, caps=caps3,
                     slots=(c["slots"] if static.timed
                            else {k: work[k] for k in slot_keys}),
                     pon_idx=pon_idx,
                     pon_end=pon_end, poff_idx=poff_idx, poff_end=poff_end,
                     pend_caps=pend_caps, pend_mask=pend_mask,
                     pend_cnt=pend_cnt,
                     n_changes=c["n_changes"] + changes.astype(jnp.int32),
                     balance_trips=(c["balance_trips"]
                                    + jnp.asarray(trips, jnp.int32)),
                     evac_trips=c["evac_trips"] + jnp.int32(evac_trips),
                     # Timed cells count vMotions at commit time (the
                     # object plane counts at completion); all launches
                     # eventually commit -- transfers are oblivious to
                     # endpoint power flips -- so totals agree.
                     vmotions=(c["vmotions"] if static.timed
                               else c["vmotions"] + vmot),
                     slot_pressure=c["slot_pressure"] | mig_pressure
                     | (maybe_off & pressure))
            if static.timed:
                c = dict(c, mig_src=mig_src, mig_j=mig_j, mig_dst=mig_dst,
                         mig_end=mig_end, mig_prev=mig_prev,
                         poff_wait=poff_wait)
            return c

        def _apply_remap(work, move, victim, order, dests):
            """Move the victim's occupied slots to their destinations'
            first free slots, restoring pad values behind them (one shared
            ``move_slot`` per evacuee, so holes left by balancer moves are
            reused correctly)."""
            def body(k, w):
                j = jnp.take_along_axis(
                    order, jnp.full((S, 1), k, order.dtype), axis=-1)[..., 0]
                dest = jnp.take_along_axis(
                    dests, jnp.full((S, 1), k, dests.dtype), axis=-1)[..., 0]
                do = move & (dest >= 0)
                w, _ = kernels.move_slot(jnp, w, do, victim, j, dest, pads)
                return w

            return be.fori(J, body, work)

        # ----------------------------------------------------------- step
        def step(c, x):
            t, in_win = x
            # Counter values at step entry: the per-tick action counts the
            # timeseries path emits are end-minus-start deltas, so they sum
            # (exactly, as ints) back to the carried totals.
            prev_counts = {k: c[k] for k in ("n_changes", "vmotions",
                                             "power_ons", "power_offs")}

            with scope("lifecycle"):
                # 1. Scripted host lifecycle events.  A returning host boots
                # with at most the unallocated budget as its cap (the manager
                # may have reabsorbed its watts while it was away); a grant
                # held by a host whose power-on is still in flight counts as
                # allocated, like the budget invariant counts it.
                on, last_cfg, ev_done = c["on"], c["last_cfg"], c["ev_done"]
                caps = c["caps"]
                pend_grant = jnp.where(
                    c["pon_idx"] >= 0,
                    gather_host(caps, jnp.clip(c["pon_idx"], 0, H - 1)), 0.0)
                for e in range(static.n_events):
                    due = ~ev_done[:, e] & (a["ev_t"][:, e] <= t)
                    eh = a["ev_host"][:, e]
                    target = a["ev_on"][:, e]
                    cur = gather_host(on, eh)
                    onehot = h_idx[None, :] == eh[..., None]
                    boot = due & target & ~cur
                    pool = jnp.maximum(
                        a["budget"] - jnp.sum(caps * on, axis=-1) - pend_grant,
                        0.0)
                    caps = jnp.where(
                        boot[:, None] & onehot,
                        jnp.minimum(caps, pool[:, None]), caps)
                    if tcols is not None:
                        # The returning host's cap must also fit its ancestor
                        # headroom, with the pending power-on grant counted as
                        # allocated (Simulator._apply_power_events).
                        pend_on = ((c["pon_idx"] >= 0)[:, None]
                                   & (h_idx[None, :] == c["pon_idx"][:, None]))
                        head = kernels.tree_headroom(jnp, tcols, on | pend_on,
                                                     caps)
                        anc_b = kernels.tree_anc_at(jnp, tcols, eh)
                        room = jnp.min(jnp.where(anc_b, head, jnp.inf),
                                       axis=-1)
                        caps = jnp.where(
                            boot[:, None] & onehot,
                            jnp.minimum(caps,
                                        jnp.maximum(room, 0.0)[:, None]), caps)
                    on = jnp.where((due & target)[:, None] & onehot, True, on)
                    on = jnp.where((due & ~target)[:, None] & onehot, False,
                                   on)
                    last_cfg = jnp.where(due & (cur != target), t, last_cfg)
                    ev_done = ev_done.at[:, e].set(ev_done[:, e] | due)

                # 2. Pending power-on/off timers come due.
                pon_fire = (c["pon_idx"] >= 0) & (t >= c["pon_end"])
                on = on | (pon_fire[:, None]
                           & (h_idx[None, :] == c["pon_idx"][..., None]))
                poff_fire = (c["poff_idx"] >= 0) & (t >= c["poff_end"])
                if static.timed:
                    # A power-off waiting on its evacuation holds a stale
                    # ``poff_end``; its timer starts when the table drains.
                    poff_fire = poff_fire & ~c["poff_wait"]
                on = on & ~(poff_fire[:, None]
                            & (h_idx[None, :] == c["poff_idx"][..., None]))
                # Apply only the hosts the deferred cap *actions* set (the
                # emitted-change mask), not the whole decision-time column: a
                # host a scripted event booted during the pending window had
                # no action and keeps its boot cap.
                caps = jnp.where(poff_fire[:, None] & c["pend_mask"],
                                 c["pend_caps"], caps)
                last_cfg = jnp.where(pon_fire | poff_fire, t, last_cfg)
                c = dict(
                    c, on=on, caps=caps, last_cfg=last_cfg, ev_done=ev_done,
                    n_changes=c["n_changes"]
                    + jnp.where(poff_fire, c["pend_cnt"], 0),
                    power_ons=c["power_ons"] + pon_fire.astype(jnp.int32),
                    power_offs=c["power_offs"] + poff_fire.astype(jnp.int32),
                    pon_idx=jnp.where(pon_fire, -1, c["pon_idx"]),
                    poff_idx=jnp.where(poff_fire, -1, c["poff_idx"]))

            # 2b. In-flight migrations commit FIFO (timed regime): each
            # due table entry replays its recorded ``move_slot`` against
            # the live layout -- in table order from the same base layout
            # as the invocation's what-if, so landing slots coincide.
            # Commits are oblivious to endpoint power state (a VM can
            # land on a host that failed or powered off mid-copy, exactly
            # like the object plane's ``move_vm``).
            if static.timed:
                with scope("vmotion/commit"):
                    def commit(cc):
                        def body(k, st):
                            slots, msrc, nmig = st
                            src = cc["mig_src"][:, k]
                            due = (src >= 0) & (cc["mig_end"][:, k] <= t)
                            slots, _ = kernels.move_slot(
                                jnp, slots, due, src, cc["mig_j"][:, k],
                                cc["mig_dst"][:, k], pads)
                            msrc = msrc.at[:, k].set(jnp.where(due, -1, src))
                            return slots, msrc, nmig + due.astype(jnp.int32)
                        slots, msrc, nmig = be.fori(
                            M, body, (cc["slots"], cc["mig_src"],
                                      jnp.zeros(S, dtype=jnp.int32)))
                        return dict(cc, slots=slots, mig_src=msrc,
                                    vmotions=cc["vmotions"] + nmig)

                    c = jax.lax.cond(
                        jnp.any((c["mig_src"] >= 0) & (c["mig_end"] <= t)),
                        commit, lambda cc: cc, c)
                    # Evacuation entries committed => the deferred power-off's
                    # prerequisites are met: start its latency timer now
                    # (object plane: ``_complete_actions`` then
                    # ``_start_actions`` in the same tick).
                    drained = ~jnp.any(c["mig_src"] >= 0, axis=-1)
                    start_off = c["poff_wait"] & drained
                    c = dict(c, poff_wait=c["poff_wait"] & ~start_off,
                             poff_end=jnp.where(
                                 start_off, t + static.power_off_latency_s,
                                 c["poff_end"]))

            # 3. Manager invocation on the carried DRS schedule; deferred
            # per cell while its power actions are in flight.
            outstanding = (c["pon_idx"] >= 0) | (c["poff_idx"] >= 0)
            if static.timed:
                outstanding = outstanding | ~drained
            can = (t >= c["next_drs"]) & ~outstanding
            c = dict(c, next_drs=jnp.where(
                can, t + static.drs_period_s,
                jnp.where(t >= c["next_drs"], t + dt, c["next_drs"])))
            c = jax.lax.cond(
                jnp.any(can),
                lambda cc: invocation(cc, can, t),
                lambda cc: cc, c)

            # 4. Demands at t from the (possibly just remapped) trace
            # slots, then delivery + accounting at the post-invocation
            # state.
            cpu, mem = demands(t, trace=c["slots"])
            on, caps = c["on"], c["caps"]
            hosts = hosts_of(on)
            active = c["slots"]["occ"] & on[..., None]
            overhead = None
            if static.timed:
                with scope("vmotion/overhead"):
                    # Endpoint vMotion overhead from the (post-invocation)
                    # in-flight table: each entry charges its destination and
                    # its VM's *current* host.  For chained launches that is
                    # the earliest uncommitted leg's source -- commits drain
                    # FIFO, so the committed prefix never interleaves and a
                    # bounded predecessor walk finds it.
                    act_m = c["mig_src"] >= 0
                    eff_src, prev = c["mig_src"], c["mig_prev"]

                    def hop(_, st):
                        eff_src, prev = st
                        pc = jnp.clip(prev, 0, M - 1)
                        live = (prev >= 0) & jnp.take_along_axis(act_m, pc,
                                                                 axis=-1)
                        eff_src = jnp.where(
                            live,
                            jnp.take_along_axis(c["mig_src"], pc, axis=-1),
                            eff_src)
                        prev = jnp.where(
                            live,
                            jnp.take_along_axis(c["mig_prev"], pc, axis=-1),
                            jnp.full_like(prev, -1))
                        return eff_src, prev

                    eff_src, _ = be.fori(M, hop, (eff_src, prev))
                    ep = ((eff_src[..., None] == h_idx[None, None, :])
                          | (c["mig_dst"][..., None] == h_idx[None, None, :]))
                    overhead = static.vmotion_overhead_mhz * jnp.sum(
                        act_m[..., None] & ep, axis=1)
            with scope("deliver"):
                tick, tp, td, mem_dem_h = deliver(
                    hosts, caps, on, active, c["slots"]["weights"],
                    c["slots"]["reservation"], c["slots"]["limit"],
                    c["slots"]["tag_masks"], cpu, mem, overhead=overhead)

                # Budget invariant: powered-on caps plus the cap of a host
                # whose power-on is pending (it holds its grant while
                # joining).
                pend_cap = jnp.where(
                    c["pon_idx"] >= 0,
                    gather_host(caps, jnp.clip(c["pon_idx"], 0, H - 1)), 0.0)
                total = jnp.sum(caps * on, axis=-1) + pend_cap
                if tcols is not None:
                    # Per-node invariant with the pending power-on target
                    # counted as allocated (its grant is its already-set cap).
                    tree_mask = on | (
                        (c["pon_idx"] >= 0)[:, None]
                        & (h_idx[None, :] == c["pon_idx"][:, None]))
                    node_over = (kernels.tree_node_sums(jnp, tcols, tree_mask,
                                                        caps)
                                 - tcols.limit)
                    over_tree = jnp.maximum(c["over_tree"],
                                            jnp.max(node_over, axis=-1))

            with scope("dpm/trigger"):
                # 6. DPM low-watermark tracking at delivered capacity, through
                # the same utilization kernel the invocation's triggers use.
                eff = jnp.clip(cpu, c["slots"]["reservation"],
                               c["slots"]["limit"])
                eff_h = jnp.sum(jnp.where(active, eff, 0.0), axis=-1)
                cpu_util, mem_util = kernels.host_utilizations(
                    jnp, hosts, caps, eff_h, mem_dem_h, host_mem_spec)
                low = on & (cpu_util < dpmp.low_util) & (
                    mem_util < dpmp.low_util)
                entering = low & jnp.isnan(c["low_since"])
                low_since = jnp.where(entering, t, c["low_since"])
                low_since = jnp.where(on & ~low, jnp.nan, low_since)

            with scope("deliver"):
                c = dict(
                    c, low_since=low_since,
                    acc={k: c["acc"][k] + tick[k] * dt for k in c["acc"]},
                    win={k: c["win"][k] + jnp.where(in_win, tick[k], 0.0) * dt
                         for k in c["win"]},
                    tag_pay=c["tag_pay"] + tp * dt,
                    tag_dem=c["tag_dem"] + td * dt,
                    over_budget=jnp.maximum(c["over_budget"],
                                            total - a["budget"]))
                if tcols is not None:
                    c["over_tree"] = over_tree
            if not static.keep_timeseries:
                return c, None
            return c, dict(
                tick,
                cap_changes=c["n_changes"] - prev_counts["n_changes"],
                vmotions=c["vmotions"] - prev_counts["vmotions"],
                power_ons=c["power_ons"] - prev_counts["power_ons"],
                power_offs=c["power_offs"] - prev_counts["power_offs"])

        zeros = {k: jnp.zeros(S) for k in FIELDS}
        zi = jnp.zeros(S, dtype=jnp.int32)
        init = {
            "caps": a["caps0"], "on": a["on"],
            "slots": {k: a[k] for k in slot_keys},
            "low_since": jnp.full((S, H), jnp.nan),
            "last_cfg": jnp.full(S, -1e18),
            "next_drs": jnp.full(S, static.drs_first_at_s),
            "pon_idx": jnp.full(S, -1, dtype=jnp.int64),
            "pon_end": jnp.zeros(S),
            "poff_idx": jnp.full(S, -1, dtype=jnp.int64),
            "poff_end": jnp.zeros(S),
            "pend_caps": a["caps0"], "pend_cnt": zi,
            "pend_mask": jnp.zeros((S, H), dtype=bool),
            "ev_done": jnp.zeros((S, static.n_events), dtype=bool),
            "acc": dict(zeros), "win": dict(zeros),
            "tag_pay": jnp.zeros((S, static.n_tags)),
            "tag_dem": jnp.zeros((S, static.n_tags)),
            "n_changes": zi, "vmotions": zi,
            "power_ons": zi, "power_offs": zi,
            "over_budget": jnp.full(S, -jnp.inf),
            "slot_pressure": jnp.zeros(S, dtype=bool),
            "balance_trips": jnp.int32(0),
            "evac_trips": jnp.int32(0),
        }
        if tcols is not None:
            init["over_tree"] = jnp.full(S, -jnp.inf)
        if static.timed:
            init.update({
                "mig_src": jnp.full((S, M), -1, dtype=jnp.int64),
                "mig_j": jnp.full((S, M), -1, dtype=jnp.int64),
                "mig_dst": jnp.full((S, M), -1, dtype=jnp.int64),
                "mig_prev": jnp.full((S, M), -1, dtype=jnp.int64),
                "mig_end": jnp.zeros((S, M)),
                "poff_wait": jnp.zeros(S, dtype=bool)})
        xs = (a["ts"], a["win_mask"])
        c, ys = jax.lax.scan(step, init, xs)
        out = {"acc": c["acc"], "win": c["win"],
               "tag_payload": c["tag_pay"], "tag_demand": c["tag_dem"],
               "cap_changes": c["n_changes"], "vmotions": c["vmotions"],
               "power_ons": c["power_ons"], "power_offs": c["power_offs"],
               "max_total_cap": c["over_budget"],
               "over_budget": c["over_budget"],
               "final_caps": c["caps"], "final_on": c["on"],
               "final_occ": c["slots"]["occ"],
               "slot_pressure": c["slot_pressure"],
               "balance_trips": c["balance_trips"][None],
               "evac_trips": c["evac_trips"][None]}
        if tcols is not None:
            out["over_tree"] = c["over_tree"]
        if static.keep_timeseries:
            out["timeseries"] = ys
        return out

    build = build_churn if static.churn else build_static

    def program(a):
        # Ops outside every phase (the tick loop's carry copies, the DRS
        # conditional, set-up before the scan) fall under ``repro/scan``.
        with scope("scan"):
            return build(a)
    return program


def _cells_specs(a, P):
    """shard_map partition specs for the packed array dict: every per-cell
    array splits on its leading S axis; the shared time axis replicates."""
    return {k: (P() if k in ("ts", "drs_mask")
                else P(None, "cells") if k == "win_mask"
                else P("cells")) for k in a}


def _out_specs(static: _StaticSpec, P):
    """shard_map output specs: per-cell results split on their leading S
    axis; the per-tick timeseries (``(T, S)``) splits on axis 1."""
    specs = {k: P("cells") for k in (
        "acc", "win", "tag_payload", "tag_demand", "cap_changes",
        "vmotions", "power_ons", "power_offs", "max_total_cap",
        "over_budget", "final_caps", "final_on", "final_occ",
        "slot_pressure", "balance_trips")}
    if static.churn:
        specs["evac_trips"] = P("cells")
    if static.n_tree_nodes:
        specs["over_tree"] = P("cells")
    if static.keep_timeseries:
        specs["timeseries"] = P(None, "cells")
    return specs


@functools.lru_cache(maxsize=None)
def _compiled_program(static: _StaticSpec, n_devices: int = 1):
    """Jit (and cache) the whole-grid program.

    The packed input dict is marked for donation: the scan carry aliases
    the transferred buffers instead of holding both live, cutting peak
    device memory on the largest cells (inputs re-transfer from the host
    copy on every call, so repeated ``run()`` stays valid).

    With ``n_devices > 1`` the program is wrapped in ``shard_map`` over the
    1-D ``cells`` mesh (``repro.launch.mesh.make_cells_mesh``): ``static``
    describes the *global* grid and each device traces the identical
    per-shard program over ``n_cells / n_devices`` cells.  Cells never
    interact -- every reduction in the scan body runs over the trailing
    host/slot axes -- so the mapped body contains no collectives; the only
    cross-device traffic is the final gather of the per-cell accumulators
    when results leave the mesh.
    """
    import jax

    if n_devices <= 1:
        return jax.jit(_build_program(static), donate_argnums=0)
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_cells_mesh

    if static.n_cells % n_devices:
        raise ValueError(  # BatchedSimulator.run pads the cells axis first
            f"{static.n_cells} cells not divisible by {n_devices} devices")
    local = static._replace(n_cells=static.n_cells // n_devices)
    program = _build_program(local)
    mesh = make_cells_mesh(n_devices)

    def sharded(a):
        return jax.shard_map(program, mesh=mesh,
                             in_specs=(_cells_specs(a, P),),
                             out_specs=_out_specs(static, P),
                             check_vma=False)(a)

    return jax.jit(sharded, donate_argnums=0)


#: AOT-compiled executables keyed by (static, n_devices, input-shape
#: signature): ``BatchedSimulator.compile`` populates it -- concurrently
#: from the sweep pipeline's worker threads -- and ``run_async`` dispatches
#: against it without re-tracing.
_AOT_EXECUTABLES: dict = {}
_AOT_LOCK = threading.Lock()


@contextlib.contextmanager
def _quiet_donation():
    """Suppress XLA's "donated buffers were not usable" advisory: shared
    time-axis inputs (``ts``/``drs_mask``) and sub-word masks have no
    aliasable output, which is expected, not actionable."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


class BatchedSimulator:
    """Simulate S scenario cells as one compiled program.

    Cells must share the time grid (``duration_s``/``tick_s``) and DRS
    schedule; host counts, VM counts, traces, budgets, policies, windows,
    DPM flags, and scripted power events vary freely per cell (smaller
    cells are padded).

    ``waterfill_iters`` defaults to 100: the lockstep bisection reaches its
    float64 fixed point in ~60 trips for realistic magnitudes, so this
    matches the NumPy primitive's 200-trip result exactly at half the cost.

    ``slot_slack`` over-provisions the per-host VM slot axis for dynamic
    grids so DPM evacuations and balancer/correction migrations have
    somewhere to land; if a run's consolidation would exceed it, the engine
    raises after the run (``slot_pressure``) rather than silently diverging.

    ``balancer`` (a ``kernels.MigrationParams``) enables the hill-climb
    migration balancer for cells with ``balancer_enabled`` -- the batched
    twin of the manager's ``BalancerConfig``; the default (``max_moves=0``)
    matches the sweep regime with migration search disabled.

    ``n_devices`` shards the S-cells axis over a 1-D ``cells`` mesh
    (``shard_map``): ``None`` uses every visible jax device, ``1`` pins the
    single-device program.  Cells are embarrassingly parallel, so each
    device runs its shard through the identical compiled scan and per-cell
    results are bit-identical to the single-device run; when the cell count
    is not a device multiple the cells axis is padded with duplicates of the
    leading cells (dropped from the results).

    ``pad_hosts`` / ``pad_slots`` force the packed host axis (and the
    pre-slack slot axis) up to at least the given sizes -- the sweep
    layer's pad-bucketing uses them to pin every grid in a pow2 shape
    class to the same compiled program.

    ``span_ids`` (e.g. ``{"sweep": 3, "bucket": 0}``) tag the simulator's
    host spans in a profiler trace (``repro.sim.spans``); the spans' times
    land in each :class:`BatchResult`'s ``spans``.
    """

    def __init__(self, cells: Sequence[BatchCell],
                 balance: Optional[kernels.BalanceParams] = None,
                 dpm: Optional[kernels.DPMParams] = None,
                 waterfill_iters: int = 100,
                 slot_slack: float = 2.0,
                 balancer: Optional[kernels.MigrationParams] = None,
                 n_devices: Optional[int] = None,
                 pad_hosts: int = 0,
                 pad_slots: int = 0,
                 keep_timeseries: bool = False,
                 span_ids: Optional[dict] = None):
        if not cells:
            raise ValueError("no cells")
        self.cells = list(cells)
        self._span_ids = dict(span_ids or {})
        # Spans not yet reported by a dispatch: the pack below, and a
        # compile on an AOT miss.  The next ``run_async`` takes them over.
        self._spans: dict = {}
        self.config = cells[0].config
        self._n_devices = n_devices
        self._keep_timeseries = bool(keep_timeseries)
        self._pad_hosts = int(pad_hosts)
        self._pad_slots = int(pad_slots)
        self._balancer = balancer or kernels.MigrationParams(max_moves=0)
        self._churn = any(c.dpm_enabled or c.config.power_events
                          for c in cells)
        # The migration layer compiles in when the grid can actually move a
        # VM: rule violations to correct at t=0, a live hill-climb
        # balancer, or rules that DPM evacuations might have to respect
        # (and whose affinity groups a later correction must re-gather).
        has_rules = any(c.snapshot.rules for c in cells)
        violated = any(rules_mod.all_violations(c.snapshot)
                       for c in cells)
        balancer_live = (self._balancer.max_moves > 0
                         and any(c.balancer_enabled for c in cells))
        self._migration = (balancer_live or violated
                           or (has_rules
                               and any(c.dpm_enabled for c in cells)))
        self._dynamic = self._churn or self._migration
        self._validate()
        # Timed-vMotion regime: the migration-capable cells run the copy
        # window + FIFO-commit model (gated launches, endpoint overhead)
        # instead of atomic remaps.
        self._timed = (self._mig_ref is not None
                       and not self._mig_ref.instant_migrations)
        with span("batch.pack", self._spans, **self._span_ids):
            self._pack(balance or kernels.BalanceParams(),
                       dpm or kernels.DPMParams(), waterfill_iters,
                       slot_slack)
        self.pack_s = self._spans["batch.pack"]

    # ---------------------------------------------------------- validation
    @staticmethod
    def _mig_capable(c: BatchCell,
                     balancer: kernels.MigrationParams) -> bool:
        """Whether this cell can actually move a VM -- and therefore cares
        about the migration execution model (instant vs timed vMotion)."""
        return bool(c.dpm_enabled
                    or (balancer.max_moves > 0 and c.balancer_enabled)
                    or (c.snapshot.rules
                        and rules_mod.all_violations(c.snapshot)))

    @classmethod
    def _cell_reason(cls, c: BatchCell, ref: SimConfig, churn: bool,
                     balancer: kernels.MigrationParams,
                     check_traces: bool = False,
                     ref_mig: Optional[SimConfig] = None) -> Optional[str]:
        """Why this cell cannot join a batch anchored on ``ref`` (None if
        it can).  ``ref_mig`` is the migration-model anchor: the config of
        the first migration-capable cell already admitted (the model is
        compiled into the program, so all such cells must agree on it)."""
        same = (c.config.duration_s == ref.duration_s
                and c.config.tick_s == ref.tick_s
                and c.config.drs_period_s == ref.drs_period_s
                and c.config.drs_first_at_s == ref.drs_first_at_s)
        if not same:
            return "disagrees on the shared time grid"
        if cls._mig_capable(c, balancer):
            if (not c.config.instant_migrations
                    and not c.config.migration_gated):
                return ("timed migrations in the batched engine need "
                        "launch gating (set migration_slots_per_host "
                        "and/or migration_bandwidth, and use the same on "
                        "the reference engine); ungated timed cells run "
                        "on the vector engine")
            if ref_mig is not None:
                mine = (c.config.instant_migrations,
                        c.config.vmotion_rate_mb_s,
                        c.config.vmotion_overhead_mhz,
                        c.config.migration_slots_per_host,
                        c.config.migration_bandwidth)
                want = (ref_mig.instant_migrations,
                        ref_mig.vmotion_rate_mb_s,
                        ref_mig.vmotion_overhead_mhz,
                        ref_mig.migration_slots_per_host,
                        ref_mig.migration_bandwidth)
                if mine != want:
                    return ("disagrees on the migration execution model "
                            "(instant/timed, vMotion rate/overhead, and "
                            "launch gates are shared across a batch)")
        if churn:
            same = (c.config.power_on_latency_s == ref.power_on_latency_s
                    and c.config.power_off_latency_s
                    == ref.power_off_latency_s)
            if not same:
                return ("disagrees on power latencies (shared across a "
                        "capacity-churn batch)")
        for t, host_id, _ in c.config.power_events:
            if host_id not in c.snapshot.hosts:
                return f"power event at t={t} targets unknown host {host_id!r}"
        if c.snapshot.effective_tree() is not None and c.snapshot.rules:
            return ("budget trees with placement rules cannot be batched "
                    "(constraint correction's cap funding is tree-unaware); "
                    "such cells run on the vector engine")
        if check_traces:
            bank = c.trace_bank
            if bank is None:
                bank = TraceBank.from_traces(c.traces,
                                             list(c.snapshot.vms))
            if bank.fallback:
                return "traces without a declarative spec cannot be batched"
        return None

    @classmethod
    def unsupported_cells(cls, cells: Sequence[BatchCell],
                          balancer: Optional[kernels.MigrationParams] = None
                          ) -> dict[str, str]:
        """Map of cell name -> reason for every cell the batched engine
        cannot replay, anchored on the first supportable cell's time grid.
        Used by ``run_sweep``'s per-cell fallback partitioning."""
        balancer = balancer or kernels.MigrationParams(max_moves=0)
        churn = any(c.dpm_enabled or c.config.power_events for c in cells)
        out: dict[str, str] = {}
        ref: Optional[SimConfig] = None
        ref_mig: Optional[SimConfig] = None
        for c in cells:
            capable = cls._mig_capable(c, balancer)
            reason = cls._cell_reason(c, ref or c.config, churn, balancer,
                                      check_traces=True,
                                      ref_mig=ref_mig if capable else None)
            if reason is None:
                if ref is None:
                    ref = c.config
                if capable and ref_mig is None:
                    ref_mig = c.config
            else:
                out[c.name] = reason
        return out

    def _validate(self) -> None:
        """Reject regimes the jitted program cannot replay exactly, loudly
        (the alternative -- freezing the unsupported dimension -- produces
        plausible-looking wrong results)."""
        ref_mig: Optional[SimConfig] = None
        for c in self.cells:
            capable = self._mig_capable(c, self._balancer)
            reason = self._cell_reason(c, self.config, self._churn,
                                       self._balancer,
                                       ref_mig=ref_mig if capable else None)
            if reason is not None:
                raise BatchUnsupported(f"cell {c.name!r}: {reason}")
            if capable and ref_mig is None:
                ref_mig = c.config
        # Migration-model anchor: the config every migration-capable cell
        # agreed with (None when nothing in the grid can move a VM).
        self._mig_ref = ref_mig

    # ------------------------------------------------------------- packing
    def _pack(self, balance: kernels.BalanceParams,
              dpm: kernels.DPMParams, waterfill_iters: int,
              slot_slack: float) -> None:
        cells = self.cells
        S = len(cells)
        H = max(max(len(c.snapshot.hosts) for c in cells), self._pad_hosts)
        ts, drs_mask = _drs_schedule(self.config)
        T = ts.shape[0]

        # Pass 1: per-cell VM columns and the dense slot assignment.  Each
        # cell's placed, powered-on VMs are grouped under their resident
        # host (a VM on a powered-off host occupies a slot but delivers
        # nothing until the host comes on -- the object engines'
        # active-mask semantics).  All per-VM work is vectorized: one stable
        # sort by host index yields every VM's (host, slot) coordinate.
        prepped = []
        n_bps = 1
        rmeta = kernels.RulesMeta()
        pack_rules = self._migration and any(c.snapshot.rules
                                             for c in cells)
        for c in cells:
            snap = c.snapshot
            vms, order, hj, slot, counts = dense_slot_assignment(snap, H)
            vm_ids = [v.vm_id for v in vms]

            # ``trace_bank`` rows index ``list(snap.vms)`` -- the same
            # order ``dense_slot_assignment`` returned in ``vms``.
            bank = c.trace_bank
            if bank is None:
                bank = TraceBank.from_traces(c.traces, vm_ids)
            if bank.fallback:
                bad = [vm_ids[r] for r, _ in bank.fallback]
                raise BatchUnsupported(
                    f"cell {c.name!r}: traces without a declarative spec "
                    f"cannot be batched: {bad[:5]}")
            if bank.rows.size:
                n_bps = max(n_bps, bank.bps.shape[1])
            pack = None
            if pack_rules:
                pack = RulesPack.from_rules(
                    snap.rules, {v: i for i, v in enumerate(vm_ids)},
                    {hid: j for j, hid in enumerate(snap.hosts)})
                # Grid bounds: fieldwise max of every cell's static shape.
                rmeta = kernels.RulesMeta(
                    *(max(a, b) for a, b in zip(rmeta, pack.meta())))
            prepped.append((vms, bank, order, hj, slot, counts, pack))
        J = max(max((int(p[5].max()) for p in prepped if p[5].size),
                    default=1), 1, self._pad_slots)
        if (self._churn and any(c.dpm_enabled for c in cells)) \
                or self._migration:
            # Headroom for consolidation and balancer moves: migrating VMs
            # land in free slots.
            J = int(math.ceil(J * max(slot_slack, 1.0)))

        tag_names = sorted({t for c in cells
                            for v in c.snapshot.vms.values() for t in v.tags})
        G = len(tag_names)
        E = max([len(c.config.power_events) for c in cells] + [1])
        # Hierarchical budgets: pad every cell to the widest tree.  A
        # tree-less cell in a tree batch keeps the padded defaults (no
        # ancestors, infinite limits), which make every tree op a provable
        # no-op -- its caps replay bit-identically to a tree-free batch.
        trees = [c.snapshot.effective_tree() for c in cells]
        n_tree = max((t.n_nodes for t in trees if t is not None), default=0)

        def host_col(fill=0.0):
            return np.full((S, H), fill, dtype=np.float64)

        a = {
            "on": np.zeros((S, H), dtype=bool),
            "exists": np.zeros((S, H), dtype=bool),
            # Padded hosts keep a nonzero idle->peak range so Eq. 3 stays
            # finite; the `on`/`exists` masks zero everything they produce.
            "idle": host_col(1.0), "peak": host_col(2.0),
            "cap_peak": host_col(1.0), "hyp": host_col(0.0),
            "host_mem": host_col(0.0), "caps0": host_col(0.0),
            "cpu_res": host_col(0.0),
            "budget": np.zeros(S), "enabled": np.zeros(S, dtype=bool),
            "dpm": np.zeros(S, dtype=bool),
            "bal_on": np.zeros(S, dtype=bool),
            "occ": np.zeros((S, H, J), dtype=bool),
            # Global VM index (the cell's ArrayView order) of each slot's
            # resident, -1 when empty: host reductions that must match the
            # object plane's bincount add in this order, not slot order.
            "vm": np.full((S, H, J), -1, dtype=np.int64),
            "reservation": np.zeros((S, H, J)),
            "limit": np.full((S, H, J), np.inf),
            "weights": np.full((S, H, J), 1e-12),
            "migratable": np.ones((S, H, J), dtype=bool),
            "tag_masks": np.zeros((S, H, J, G), dtype=bool),
            "bps": np.full((S, H, J, n_bps), np.inf),
            "cpu_vals": np.zeros((S, H, J, n_bps)),
            "mem_vals": np.zeros((S, H, J, n_bps)),
            "period": np.full((S, H, J), np.inf),
            "ev_t": np.full((S, E), np.inf),
            "ev_host": np.zeros((S, E), dtype=np.int64),
            "ev_on": np.zeros((S, E), dtype=bool),
            "ts": ts, "drs_mask": drs_mask,
            "win_mask": np.zeros((T, S), dtype=bool),
        }
        a["bps"][..., 0] = 0.0
        if n_tree:
            a["tree_anc"] = np.zeros((S, H, n_tree), dtype=bool)
            a["tree_limit"] = np.full((S, n_tree), np.inf)
            a["tree_depth"] = np.full((S, n_tree), -1, dtype=np.int64)
        # Rule columns only exist when some cell actually has that rule
        # kind -- absent columns skip their admission term entirely.
        if pack_rules and rmeta.n_groups:
            a["aff_group"] = np.full((S, H, J), -1, dtype=np.int64)
        if pack_rules and rmeta.n_vmhost:
            a["allowed"] = np.ones((S, H, J, H), dtype=bool)
        if pack_rules and rmeta.n_anti:
            a["anti"] = np.zeros((S, H, J, rmeta.n_anti), dtype=bool)

        for i, c in enumerate(cells):
            snap = c.snapshot
            vms, bank, order, hj, slot, counts, pack = prepped[i]
            host_idx = {hid: j for j, hid in enumerate(snap.hosts)}
            for j, h in enumerate(snap.hosts.values()):
                a["on"][i, j] = h.powered_on
                a["exists"][i, j] = True
                a["idle"][i, j] = h.spec.power_idle
                a["peak"][i, j] = h.spec.power_peak
                a["cap_peak"][i, j] = h.spec.capacity_peak
                a["hyp"][i, j] = h.spec.hypervisor_overhead
                a["host_mem"][i, j] = h.spec.memory_mb
                a["caps0"][i, j] = h.power_cap
            n = len(vms)
            res = np.array([v.reservation for v in vms])
            a["occ"][i, hj, slot] = True
            a["vm"][i, hj, slot] = order
            a["reservation"][i, hj, slot] = res[order]
            a["limit"][i, hj, slot] = np.array([v.limit for v in vms])[order]
            a["weights"][i, hj, slot] = np.maximum(
                np.array([v.shares for v in vms]), 1e-12)[order]
            a["migratable"][i, hj, slot] = np.array(
                [v.migratable for v in vms], dtype=bool)[order]
            host_on = np.zeros(H, dtype=bool)
            host_on[:len(snap.hosts)] = [h.powered_on
                                         for h in snap.hosts.values()]
            a["cpu_res"][i, :] = np.where(
                host_on, np.bincount(hj, weights=res[order], minlength=H), 0.0)
            for g, tag in enumerate(tag_names):
                tagged = np.array([tag in v.tags for v in vms], dtype=bool)
                a["tag_masks"][i, hj, slot, g] = tagged[order]
            if pack_rules:
                h_c = len(snap.hosts)
                if "aff_group" in a:
                    a["aff_group"][i, hj, slot] = pack.affinity_group[order]
                if "allowed" in a:
                    a["allowed"][i, hj, slot, :h_c] = pack.allowed[order]
                if "anti" in a and pack.n_anti:
                    a["anti"][i, hj, slot, :pack.n_anti] = \
                        pack.anti_member.T[order]
            # Demand traces in TraceBank's padded step-function layout;
            # trace-less VMs freeze at their initial demand.
            dem0 = np.array([v.demand for v in vms])
            mem0 = np.array([v.mem_demand for v in vms])
            bps = np.full((n, n_bps), np.inf)
            bps[:, 0] = 0.0
            cpu = np.repeat(dem0[:, None], n_bps, axis=1)
            mem = np.repeat(mem0[:, None], n_bps, axis=1)
            period = np.full(n, np.inf)
            if bank.rows.size:
                k = bank.bps.shape[1]
                bps[bank.rows, :k] = bank.bps
                cpu[bank.rows, :k] = bank.cpu_vals
                mem[bank.rows, :k] = bank.mem_vals
                cpu[bank.rows, k:] = bank.cpu_vals[:, -1:]
                mem[bank.rows, k:] = bank.mem_vals[:, -1:]
                period[bank.rows] = bank.period
            a["bps"][i, hj, slot] = bps[order]
            a["cpu_vals"][i, hj, slot] = cpu[order]
            a["mem_vals"][i, hj, slot] = mem[order]
            a["period"][i, hj, slot] = period[order]
            a["budget"][i] = snap.power_budget
            if n_tree and trees[i] is not None:
                tree = trees[i]
                h_c = len(snap.hosts)
                a["tree_anc"][i, :h_c, :tree.n_nodes] = tree.host_anc
                a["tree_limit"][i, :tree.n_nodes] = tree.limit
                a["tree_depth"][i, :tree.n_nodes] = tree.depth
            a["enabled"][i] = c.powercap_enabled
            a["dpm"][i] = c.dpm_enabled
            a["bal_on"][i] = c.balancer_enabled
            for e, (ev_t, host_id, on) in enumerate(
                    sorted(c.config.power_events)):
                a["ev_t"][i, e] = ev_t
                a["ev_host"][i, e] = host_idx[host_id]
                a["ev_on"][i, e] = bool(on)
            if c.window is not None:
                w0, w1 = c.window
                a["win_mask"][:, i] = (w0 <= ts) & (ts < w1)
        self._arrays = a
        self._tag_names = tag_names
        # Migration execution model (shared by every migration-capable
        # cell, enforced by _validate): launch gates apply to gated
        # instant grids too; the in-flight table sizes to the worst-case
        # launches of one invocation (correction + balancer, capped by
        # the cluster bandwidth gate, plus a full evacuation).
        limits = kernels.MigrationLimits()
        rate, ovh, mig_table = 128.0, 1500.0, 1
        if self._mig_ref is not None:
            limits = kernels.MigrationLimits(
                slots_per_host=self._mig_ref.migration_slots_per_host,
                bandwidth=self._mig_ref.migration_bandwidth)
            rate = self._mig_ref.vmotion_rate_mb_s
            ovh = self._mig_ref.vmotion_overhead_mhz
        if self._timed:
            corr_b = (rmeta.move_bound
                      if self._migration and rmeta.any else 0)
            bal_b = (self._balancer.max_moves
                     if self._migration and self._balancer.max_moves > 0
                     else 0)
            mgr_b = corr_b + bal_b
            if limits.bandwidth is not None:
                mgr_b = min(mgr_b, limits.bandwidth)
            mig_table = max(mgr_b + J, 1)
        self._static = _StaticSpec(
            n_cells=S, n_hosts=H, n_slots=J, n_tags=G, n_events=E,
            tick_s=self.config.tick_s, waterfill_iters=waterfill_iters,
            balance=balance, churn=self._dynamic, dpm=dpm,
            drs_period_s=self.config.drs_period_s,
            drs_first_at_s=self.config.drs_first_at_s,
            power_on_latency_s=self.config.power_on_latency_s,
            power_off_latency_s=self.config.power_off_latency_s,
            migration=self._migration,
            rules=rmeta if self._migration else kernels.RulesMeta(),
            balancer=self._balancer,
            timed=self._timed, mig_table=mig_table, limits=limits,
            vmotion_rate_mb_s=rate, vmotion_overhead_mhz=ovh,
            executor=backend_mod.executor_name(),
            keep_timeseries=self._keep_timeseries,
            n_tree_nodes=n_tree)
        self._ticks = T
        self._prepared = None

    # ------------------------------------------------------------- running
    def _prepare(self):
        """Resolve the mesh size, pad the cells axis, and compute the AOT
        cache signature.  Cached after the first call: padding a large grid
        is not free and ``compile``/``run_async`` both need it."""
        if self._prepared is not None:
            return self._prepared
        import jax

        S = self._static.n_cells
        n_dev = (len(jax.devices()) if self._n_devices is None
                 else int(self._n_devices))
        n_dev = max(1, min(n_dev, S))
        pad = (-S) % n_dev
        static = (self._static._replace(n_cells=S + pad) if pad
                  else self._static)
        a = self._arrays
        if pad:
            # Cells are independent, so padding the axis with duplicates of
            # the leading cells (and dropping their results) is exact.
            a = {k: (v if k in ("ts", "drs_mask")
                     else np.concatenate([v, v[:, :pad]], axis=1)
                     if k == "win_mask"
                     else np.concatenate([v, v[:pad]], axis=0))
                 for k, v in a.items()}
        sig = (static, n_dev,
               tuple(sorted((k, v.shape) for k, v in a.items())))
        self._prepared = (static, n_dev, a, sig)
        return self._prepared

    def compile(self) -> None:
        """Ensure this batch's program shape is AOT-compiled.

        ``jit(...).lower(a).compile()`` lands the executable in
        :data:`_AOT_EXECUTABLES` keyed by the shape signature (the XLA
        persistent compile cache still backs the expensive part across
        processes).  A miss records a ``batch.compile`` span, which the
        next dispatch reports as its ``compile_s``; a hit records nothing.
        Thread-safe: the sweep pipeline fires one ``compile`` per shape
        class concurrently from its worker pool (``jax.enable_x64`` is
        thread-local; the executor pin is re-read from the static spec)."""
        static, n_dev, a, sig = self._prepare()
        with _AOT_LOCK:
            if sig in _AOT_EXECUTABLES:
                return
        import jax
        with span("batch.compile", self._spans, **self._span_ids), \
                jax.enable_x64(True), \
                backend_mod.executor_scope(self._static.executor), \
                _quiet_donation():
            exe = _compiled_program(static, n_dev).lower(a).compile()
        with _AOT_LOCK:
            _AOT_EXECUTABLES[sig] = exe

    def run_async(self) -> "PendingBatch":
        """Compile (if not already) and dispatch without blocking: jax
        execution is asynchronous, so this returns once the program is
        enqueued, letting the caller dispatch further batches (or keep
        packing) while the device works.  Harvest with
        :meth:`PendingBatch.result`."""
        self.compile()
        spans, self._spans = self._spans, {}
        static, n_dev, a, sig = self._prepare()
        import jax
        t0 = time.perf_counter()
        with span("batch.dispatch", spans, **self._span_ids), \
                jax.enable_x64(True), \
                backend_mod.executor_scope(self._static.executor), \
                _quiet_donation():
            raw = _AOT_EXECUTABLES[sig](a)
        return PendingBatch(sim=self, raw=raw, dispatch_t0=t0, spans=spans,
                            n_devices=n_dev)

    def run(self) -> BatchResult:
        return self.run_async().result()

    def _harvest(self, raw, dispatch_t0: float, spans: dict,
                 n_dev: int) -> BatchResult:
        """Block on the dispatched outputs, convert them, check invariants,
        and assemble the :class:`BatchResult`, recording the
        ``batch.wait``, ``batch.fetch`` and ``batch.check`` spans into
        ``spans``, the run's record."""
        import jax
        S = self._static.n_cells
        with span("batch.wait", spans, **self._span_ids):
            jax.block_until_ready(raw)
        with span("batch.fetch", spans, **self._span_ids):
            out = {}
            for k, v in raw.items():
                if k == "timeseries":
                    # Per-tick series are (T, S): the cells axis is axis 1.
                    out[k] = {kk: np.asarray(vv)[:, :S]
                              for kk, vv in v.items()}
                elif isinstance(v, dict):
                    out[k] = {kk: np.asarray(vv)[:S] for kk, vv in v.items()}
                else:
                    out[k] = np.asarray(v)[:S]
        run_s = time.perf_counter() - dispatch_t0
        with span("batch.check", spans, **self._span_ids):
            # Post-hoc invariants, checked in one shot for the whole grid.
            if bool(out["slot_pressure"].any()):
                bad = [self.cells[i].name
                       for i in np.nonzero(out["slot_pressure"])[0]]
                raise RuntimeError(
                    f"slot capacity bound a migration/evacuation decision in "
                    f"cells {bad[:5]}: repack with a larger slot_slack")
            if self._static.churn:
                over = out["over_budget"]
            else:
                over = out["max_total_cap"] - self._arrays["budget"]
            assert float(over.max()) <= 1e-6, (
                f"budget violated during execution: worst overshoot "
                f"{float(over.max()):.3f} W (cell "
                f"{self.cells[int(over.argmax())].name})")
            if "over_tree" in out:
                ot = out["over_tree"]
                assert float(ot.max()) <= 1e-6, (
                    f"budget tree violated during execution: worst node over "
                    f"by {float(ot.max()):.3f} W (cell "
                    f"{self.cells[int(ot.argmax())].name})")

            counters = {
                "balance_trips": int(out["balance_trips"].max()),
                "drs_invocations": int(self._arrays["drs_mask"].sum())}
            if self._static.churn:
                counters.update(evac_trips=int(out["evac_trips"].max()),
                                power_offs=int(out["power_offs"].sum()),
                                power_ons=int(out["power_ons"].sum()))
            acc = out["acc"]
            return BatchResult(
                names=[c.name for c in self.cells],
                cpu_payload_mhz_s=acc["cpu_payload_mhz_s"],
                cpu_demand_mhz_s=acc["cpu_demand_mhz_s"],
                mem_payload_mb_s=acc["mem_payload_mb_s"],
                mem_demand_mb_s=acc["mem_demand_mb_s"],
                energy_j=acc["energy_j"],
                cap_changes=out["cap_changes"],
                vmotions=out["vmotions"],
                power_ons=out["power_ons"],
                power_offs=out["power_offs"],
                tag_names=self._tag_names,
                tag_payload=out["tag_payload"],
                tag_demand=out["tag_demand"],
                window_fields=out["win"],
                has_window=np.array([c.window is not None
                                     for c in self.cells]),
                final_caps=out["final_caps"],
                final_on=out["final_on"],
                final_occ=out["final_occ"],
                ticks=self._ticks,
                n_devices=n_dev,
                compile_s=spans.get("batch.compile", 0.0),
                pack_s=self.pack_s,
                run_s=run_s,
                spans=spans,
                counters=counters,
                timeseries=out.get("timeseries"),
                tick_s=self._static.tick_s)


@dataclasses.dataclass
class PendingBatch:
    """A dispatched-but-unharvested batch: ``run_async``'s handle.

    ``raw`` holds the program's on-device output tree; ``result()`` blocks
    until execution finishes and builds the :class:`BatchResult`.  The
    sweep pipeline holds one of these per bucket so every bucket is in
    flight before any is harvested.
    """

    sim: BatchedSimulator
    raw: dict
    dispatch_t0: float
    spans: dict                  # the run's record (``BatchResult.spans``)
    n_devices: int

    def result(self) -> BatchResult:
        return self.sim._harvest(self.raw, self.dispatch_t0, self.spans,
                                 self.n_devices)
