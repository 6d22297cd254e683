"""Benchmark harness: one entry per paper table/figure + roofline summary.

Prints ``name,us_per_call,derived`` CSV rows (one per benchmark), where
``derived`` packs the table's headline numbers.  Paper-number comparisons
live in EXPERIMENTS.md.

  table2_deployments   -- paper Table II   (rack deployment trade-offs)
  table3_rebalancing   -- paper Table III  (headroom rebalancing, Sec. V-B)
  table4_standby       -- paper Table IV   (standby reallocation, Sec. V-C)
  table5_flexible      -- paper Table V    (flexible capacity, Sec. V-D)
  powercap_latency     -- cap-change vs vMotion cost asymmetry (Sec. II-D)
  sweep_scale          -- vectorized-engine scenario sweep at 10/100/1000
                          hosts (ticks/sec + CPC-vs-Static satisfaction delta)
  sweep_grid           -- the jit-compiled batched engine running a 32-cell
                          scenario grid (100 hosts x budget x spike x mix) as
                          ONE program, vs the sequential run_sweep path
  sweep_grid_dpm       -- the batched engine with the host power-state
                          dimension live: a 32-cell capacity-churn grid (DPM
                          power-off/power-on, maintenance windows, host
                          failures) as ONE program, vs sequential
  sweep_grid_rules     -- the batched engine with the migration layer live:
                          a 32-cell rule-scenario grid (affinity /
                          anti-affinity / VM-host violation bursts,
                          Fig.-1a cap-blocked corrections, hill-climb
                          balancing) as ONE program, vs sequential
  sweep_e2e            -- end-to-end sweep throughput through the
                          overlapped pipeline: the sweep_grid 32-cell
                          grid measured from SweepSpec list to merged
                          results (scenario construction + vectorized
                          TraceBank packing + AOT dispatch + harvest),
                          with the compile/pack/run cost split and the
                          e2e-vs-steady ratio the smoke gate tracks
  sweep_scale_sharded  -- the sharded sweep engine: a 256-cell grid over a
                          1-device vs 8-virtual-device ("cells",) mesh
                          (subprocess with forced host device count), plus
                          a 10k-host / 100k-VM-slot datacenter cell, via
                          benchmarks/sweep_sharded.py
  budget_service       -- hierarchical-budget control plane: event-replay
                          latency percentiles (headroom/admission queries,
                          demand updates, node-limit changes over a two-row
                          budget tree) plus headroom and row_contention
                          sweep parity
  roofline_summary     -- per-(arch x shape) roofline terms from the dry-run

Run: PYTHONPATH=src python -m benchmarks.run [--skip-slow] [--json]

``--json`` additionally writes machine-readable sweep-throughput numbers to
``BENCH_sweep.json`` (ticks/s per grid size, cells/s batched vs sequential)
so the perf trajectory is tracked across PRs.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import time

#: Structured results populated by the sweep benches, dumped by ``--json``.
ARTIFACT: dict = {}


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return (time.perf_counter() - t0) * 1e6, out


def table2_deployments():
    from repro.core.power_model import PAPER_HOST, deployment_table
    rows = deployment_table(PAPER_HOST, 8000.0, [400, 320, 285, 250])
    derived = ";".join(
        f"{int(r['power_cap_w'])}W:{r['host_count']}hosts"
        f"/cpu{r['capacity_ratio']:.2f}/mem{r['memory_ratio']:.2f}"
        for r in rows)
    return derived


def _sim_table(scenario):
    from repro.sim.experiments import run_all
    from repro.sim.metrics import ratio_table
    res = run_all(scenario)
    table = ratio_table({k: v.acc for k, v in res.items()}, "statichigh")
    return res, table


def table3_rebalancing():
    res, t = _sim_table("headroom")
    return ";".join(
        f"{p}:cpu{t[p]['cpu_payload_ratio']:.2f}/vmo{t[p]['vmotions']}"
        for p in ("cpc", "static", "statichigh"))


def table4_standby():
    res, t = _sim_table("standby")
    return ";".join(
        f"{p}:cpu{t[p]['cpu_payload_ratio']:.2f}/vmo{t[p]['vmotions']}"
        f"/pow{t[p]['power_ratio']:.2f}"
        for p in ("cpc", "static", "statichigh"))


def table5_flexible():
    res, t = _sim_table("flexible")
    return ";".join(
        f"{p}:cpu{t[p]['cpu_payload_ratio']:.2f}"
        f"/mem{t[p]['mem_payload_ratio']:.2f}"
        f"/trd{res[p].acc.tag_satisfaction('trading'):.2f}"
        for p in ("cpc", "static", "statichigh"))


def powercap_latency():
    """Sec. II-D asymmetry: cap write (<1 ms) vs vMotion (seconds).

    Reports our simulator's models of both actions for one 2 GB VM."""
    from repro.sim.cluster import SimConfig
    cfg = SimConfig()
    cap_ms = 1.0  # baseboard RPC, paper ref [4]
    vmotion_s = (2 * 1024) / cfg.vmotion_rate_mb_s
    return (f"cap:{cap_ms}ms;vmotion:{vmotion_s:.0f}s;"
            f"ratio:{vmotion_s * 1000 / cap_ms:.0f}x")


def sweep_scale():
    """Scenario sweep on the vectorized engine: 10/100/1000 hosts.

    Each cell is a host-correlated burst scenario (10 VMs per host) run
    under all three policies; reports the vector engine's throughput in
    ticks/sec, the CPC-vs-Static payload-satisfaction delta, and CPC's cap
    changes.  The 1,000-host cell simulates 10,000 VMs end-to-end."""
    from repro.sim.sweep import run_sweep, scale_ladder
    specs = scale_ladder(sizes=(10, 100, 1000), spike="burst",
                         duration_s=600.0)
    res = run_sweep(specs, policies=("cpc", "static"))
    parts = []
    ARTIFACT["sweep_scale"] = {}
    for spec in specs:
        cpc = res[spec.name]["cpc"]
        static = res[spec.name]["static"]
        ARTIFACT["sweep_scale"][str(spec.n_hosts)] = {
            "ticks_per_s": cpc.ticks_per_s,
            "dsat_cpc_vs_static":
                cpc.cpu_satisfaction - static.cpu_satisfaction,
            "cap_changes": cpc.cap_changes,
        }
        parts.append(
            f"{spec.n_hosts}h:{cpc.ticks_per_s:.0f}tps"
            f"/dsat{cpc.cpu_satisfaction - static.cpu_satisfaction:+.3f}"
            f"/caps{cpc.cap_changes}")
    return ";".join(parts)


def sweep_grid():
    """The batched engine's headline: a >=32-cell grid in one jitted program.

    Grid: 100 hosts x {230, 250} W/host x 4 spike families x {homogeneous,
    mixed} x {cpc, static} = 32 cells (32,000 VMs simulated end-to-end).
    The sequential baseline runs a 4-cell subset of the same grid through
    the per-cell ``run_sweep`` path.  Both sides report *engine* cells/s --
    simulation wall time on prepared clusters, matching ``run_cell``'s
    ``wall_s`` semantics which exclude scenario construction -- and the
    artifact also records end-to-end numbers (build + pack + run) plus the
    one-off jit compile."""
    from repro.sim.batch import BatchCell, BatchedSimulator
    from repro.sim.sweep import build_sweep, run_cell, scenario_families
    specs = scenario_families(sizes=(100,), budgets_per_host_w=(230.0, 250.0),
                              spikes=("flat", "burst", "step", "prime"),
                              heterogeneous=(False, True), duration_s=600.0)
    policies = ("cpc", "static")
    n_cells = len(specs) * len(policies)

    t0 = time.perf_counter()
    cells = []
    for spec in specs:
        for p in policies:
            snap, traces, cfg = build_sweep(spec, p)
            cells.append(BatchCell(
                name=f"{spec.name}/{p}", snapshot=snap, traces=traces,
                config=cfg, powercap_enabled=(p == "cpc")))
    sim = BatchedSimulator(cells)
    prep_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    sim.run()                                       # jit compile + first run
    first_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = sim.run()
    batch_wall = time.perf_counter() - t0
    batch_cps = n_cells / batch_wall
    # First call = compile + one execution; steady-state wall isolates the
    # execution, so the difference estimates the one-off compile cost.
    compile_wall = max(first_wall - batch_wall, 0.0)

    seq_wall, seq_cells = 0.0, 0
    t0 = time.perf_counter()
    for spec in specs[:2]:
        for p in policies:
            seq_wall += run_cell(spec, p, engine="vector").wall_s
            seq_cells += 1
    seq_e2e = time.perf_counter() - t0
    seq_cps = seq_cells / seq_wall

    i_of = {c.name: i for i, c in enumerate(cells)}
    sat = []
    for s in specs:
        cpc = res.accumulators(i_of[f"{s.name}/cpc"])
        static = res.accumulators(i_of[f"{s.name}/static"])
        sat.append(cpc.cpu_satisfaction() - static.cpu_satisfaction())
    ARTIFACT["sweep_grid"] = {
        "n_cells": n_cells,
        "n_hosts": 100,
        "cells_per_s_batched": batch_cps,
        "cells_per_s_sequential": seq_cps,
        "speedup": batch_cps / seq_cps,
        "cells_per_s_batched_e2e": n_cells / (prep_wall + batch_wall),
        "cells_per_s_sequential_e2e": seq_cells / seq_e2e,
        "compile_s": compile_wall,
        "mean_dsat_cpc_vs_static": sum(sat) / len(sat),
    }
    return (f"{n_cells}cells@100h:{batch_cps:.1f}cells/s"
            f";seq:{seq_cps:.1f}cells/s"
            f";speedup:{batch_cps / seq_cps:.1f}x"
            f";compile:{compile_wall:.1f}s")


def _pipeline_timing():
    """Summed per-bucket cost split of the most recent batched sweep call
    (see ``repro.sim.sweep.LAST_BATCH_INFO``)."""
    from repro.sim.sweep import LAST_BATCH_INFO
    return {
        "n_buckets": len(LAST_BATCH_INFO),
        "compile_s": sum(b["compile_s"] for b in LAST_BATCH_INFO),
        "pack_s": sum(b["pack_s"] for b in LAST_BATCH_INFO),
        "run_s": sum(b["run_s"] for b in LAST_BATCH_INFO),
    }


def sweep_e2e():
    """End-to-end sweep throughput: the overlapped pipeline, whole path.

    Same 32-cell grid as ``sweep_grid``, but the measured wall starts from
    the ``SweepSpec`` list: scenario construction (table-vectorized trace
    factories), ``TraceBank`` packing, AOT dispatch, and harvest all
    inside the clock -- the number a sweep user actually experiences.  A
    first call warms the AOT executables so the measured pass isolates the
    pipeline (compile cost is reported separately by ``sweep_grid``).
    Reports e2e cells/s, steady-state cells/s (device wall only), their
    ratio -- the machine-portable pipeline-efficiency metric the smoke
    gate tracks -- and the compile/pack/run split."""
    from repro.sim.sweep import run_sweep_batched, scenario_families
    specs = scenario_families(sizes=(100,), budgets_per_host_w=(230.0, 250.0),
                              spikes=("flat", "burst", "step", "prime"),
                              heterogeneous=(False, True), duration_s=600.0)
    policies = ("cpc", "static")
    n_cells = len(specs) * len(policies)

    run_sweep_batched(specs, policies=policies)     # warm AOT executables
    t0 = time.perf_counter()
    run_sweep_batched(specs, policies=policies)
    e2e_wall = time.perf_counter() - t0
    timing = _pipeline_timing()
    e2e_cps = n_cells / e2e_wall
    steady_cps = n_cells / timing["run_s"]
    ratio = e2e_cps / steady_cps
    ARTIFACT["sweep_e2e"] = {
        "n_cells": n_cells,
        "n_hosts": 100,
        "cells_per_s_e2e": e2e_cps,
        "cells_per_s_steady": steady_cps,
        "e2e_ratio": ratio,
        "e2e_wall_s": e2e_wall,
        "timing": timing,
    }
    return (f"{n_cells}cells@100h:e2e:{e2e_cps:.1f}cells/s"
            f";steady:{steady_cps:.1f}cells/s"
            f";ratio:{ratio:.2f}"
            f";pack:{timing['pack_s']:.2f}s"
            f";run:{timing['run_s']:.2f}s")


def sweep_grid_dpm():
    """Capacity churn at grid scale: the host-lifecycle dimension batched.

    Grid: 100 hosts x 4 churn families (cap-only, DPM valley/burst,
    maintenance window, host failure) x 2 spike families x {homogeneous,
    mixed} x {cpc, static} = 32 cells (32,000 VMs), every cell's DPM
    triggers, evacuations, scripted events, and powercap redistribution
    running inside ONE jitted program.  The sequential baseline runs the
    four pure-churn cells of the same grid through the per-cell vector
    path.  Cells/s semantics match ``sweep_grid`` (engine wall time on
    prepared clusters)."""
    from repro.sim.sweep import run_cell, run_sweep_batched, \
        scenario_families
    # 1500 s so the DPM valley [500, 1000) spans a full stability window
    # before a DRS tick lands in it (power-off at 900 s) and the burst
    # third trips the power-on trigger (1200 s).
    specs = scenario_families(
        sizes=(100,), budgets_per_host_w=(250.0,),
        spikes=("burst", "prime"), heterogeneous=(False, True),
        churns=("none", "dpm", "maintenance", "failure"),
        duration_s=1500.0, tick_s=15.0)
    policies = ("cpc", "static")
    n_cells = len(specs) * len(policies)

    t0 = time.perf_counter()
    res = run_sweep_batched(specs, policies=policies, slot_slack=1.5)
    first_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = run_sweep_batched(specs, policies=policies, slot_slack=1.5)
    batch_wall = time.perf_counter() - t0
    batch_cps = n_cells / sum(r.wall_s for by_p in res.values()
                              for r in by_p.values())
    compile_wall = max(first_wall - batch_wall, 0.0)

    churn_specs = [s for s in specs if s.churn == "dpm"][:2]
    seq_wall, seq_cells = 0.0, 0
    for spec in churn_specs:
        for p in policies:
            seq_wall += run_cell(spec, p, engine="vector").wall_s
            seq_cells += 1
    seq_cps = seq_cells / seq_wall

    pons = sum(r.power_ons for by_p in res.values() for r in by_p.values())
    poffs = sum(r.power_offs for by_p in res.values()
                for r in by_p.values())
    vmo = sum(r.vmotions for by_p in res.values() for r in by_p.values())
    ARTIFACT["sweep_grid_dpm"] = {
        "timing": _pipeline_timing(),
        "n_cells": n_cells,
        "n_hosts": 100,
        "cells_per_s_batched": batch_cps,
        "cells_per_s_sequential": seq_cps,
        "speedup": batch_cps / seq_cps,
        "compile_s": compile_wall,
        "power_ons": int(pons),
        "power_offs": int(poffs),
        "evacuations": int(vmo),
    }
    return (f"{n_cells}cells@100h:{batch_cps:.1f}cells/s"
            f";seq:{seq_cps:.1f}cells/s"
            f";speedup:{batch_cps / seq_cps:.1f}x"
            f";poffs:{poffs};pons:{pons};evac:{vmo}"
            f";compile:{compile_wall:.1f}s")


def sweep_grid_rules():
    """Rule-aware placement and balancing at grid scale: the migration
    dimension batched.

    Grid: 100 hosts x 2 rule families (violation burst: split affinity
    groups + co-placed anti-affinity pairs + misplaced VM-host rules;
    cap-blocked: a Fig.-1a affinity correction only fundable capacity can
    admit) x 4 spike families x {homogeneous, mixed} x {cpc, static} = 32
    cells (32,000 VMs), every cell's constraint corrections, hill-climb
    balancer moves, and powercap pipeline running inside ONE jitted
    program.  The sequential baseline runs a 4-cell subset through the
    per-cell vector path.  Cells/s semantics match ``sweep_grid`` (engine
    wall time on prepared clusters)."""
    from repro.sim.sweep import run_cell, run_sweep_batched, \
        scenario_families
    specs = scenario_families(
        sizes=(100,), budgets_per_host_w=(250.0,),
        spikes=("flat", "burst", "step", "prime"),
        heterogeneous=(False, True),
        rules=("violation_burst", "cap_blocked"),
        duration_s=600.0, tick_s=10.0)
    policies = ("cpc", "static")
    n_cells = len(specs) * len(policies)

    t0 = time.perf_counter()
    res = run_sweep_batched(specs, policies=policies, slot_slack=1.5)
    first_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = run_sweep_batched(specs, policies=policies, slot_slack=1.5)
    batch_wall = time.perf_counter() - t0
    batch_cps = n_cells / sum(r.wall_s for by_p in res.values()
                              for r in by_p.values())
    compile_wall = max(first_wall - batch_wall, 0.0)

    seq_wall, seq_cells = 0.0, 0
    for spec in specs[:2]:
        for p in policies:
            seq_wall += run_cell(spec, p, engine="vector").wall_s
            seq_cells += 1
    seq_cps = seq_cells / seq_wall

    vmo = sum(r.vmotions for by_p in res.values() for r in by_p.values())
    caps = sum(r.cap_changes for by_p in res.values()
               for r in by_p.values())
    ARTIFACT["sweep_grid_rules"] = {
        "timing": _pipeline_timing(),
        "n_cells": n_cells,
        "n_hosts": 100,
        "cells_per_s_batched": batch_cps,
        "cells_per_s_sequential": seq_cps,
        "speedup": batch_cps / seq_cps,
        "compile_s": compile_wall,
        "migrations": int(vmo),
        "cap_changes": int(caps),
    }
    return (f"{n_cells}cells@100h:{batch_cps:.1f}cells/s"
            f";seq:{seq_cps:.1f}cells/s"
            f";speedup:{batch_cps / seq_cps:.1f}x"
            f";migr:{vmo};caps:{caps}"
            f";compile:{compile_wall:.1f}s")


def sweep_grid_timed():
    """Production-realistic churn at grid scale: timed migrations batched.

    Grid: 100 hosts x {timed_churn, failure_cascade} x {no rules,
    violation burst} x 2 spike families x {homogeneous, mixed} x {cpc,
    static} = 32 cells (32,000 VMs).  Every cell runs the gated vMotion
    execution model -- multi-tick copy windows carried in the scan-state
    in-flight table, both endpoints charged transfer overhead, per-host
    migration slots plus the cluster bandwidth budget gating launches,
    deferred moves re-scored next invocation -- inside ONE jitted
    program; before this model these cells fell off the batched engine
    onto the per-cell vector path.  The sequential baseline runs a
    4-cell subset through that vector path.  Cells/s semantics match
    ``sweep_grid`` (engine wall time on prepared clusters)."""
    from repro.sim.sweep import run_cell, run_sweep_batched, \
        scenario_families
    specs = scenario_families(
        sizes=(100,), budgets_per_host_w=(250.0,),
        spikes=("burst", "prime"), heterogeneous=(False, True),
        churns=("timed_churn", "failure_cascade"),
        rules=("none", "violation_burst"),
        duration_s=600.0, tick_s=10.0)
    policies = ("cpc", "static")
    n_cells = len(specs) * len(policies)

    t0 = time.perf_counter()
    res = run_sweep_batched(specs, policies=policies, slot_slack=1.5)
    first_wall = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = run_sweep_batched(specs, policies=policies, slot_slack=1.5)
    batch_wall = time.perf_counter() - t0
    batch_cps = n_cells / sum(r.wall_s for by_p in res.values()
                              for r in by_p.values())
    compile_wall = max(first_wall - batch_wall, 0.0)

    seq_wall, seq_cells = 0.0, 0
    for spec in specs[:2]:
        for p in policies:
            seq_wall += run_cell(spec, p, engine="vector").wall_s
            seq_cells += 1
    seq_cps = seq_cells / seq_wall

    vmo = sum(r.vmotions for by_p in res.values() for r in by_p.values())
    pons = sum(r.power_ons for by_p in res.values() for r in by_p.values())
    poffs = sum(r.power_offs for by_p in res.values()
                for r in by_p.values())
    ARTIFACT["sweep_grid_timed"] = {
        "timing": _pipeline_timing(),
        "n_cells": n_cells,
        "n_hosts": 100,
        "cells_per_s_batched": batch_cps,
        "cells_per_s_sequential": seq_cps,
        "speedup": batch_cps / seq_cps,
        "compile_s": compile_wall,
        "migrations": int(vmo),
        "power_ons": int(pons),
        "power_offs": int(poffs),
    }
    return (f"{n_cells}cells@100h:{batch_cps:.1f}cells/s"
            f";seq:{seq_cps:.1f}cells/s"
            f";speedup:{batch_cps / seq_cps:.1f}x"
            f";migr:{vmo};pons:{pons};poffs:{poffs}"
            f";compile:{compile_wall:.1f}s")


def sweep_scale_sharded():
    """The sharded sweep engine: device scaling + the datacenter cell.

    Grid half: a 256-cell grid (128 specs x {cpc, static} at 10 hosts, one
    pad bucket) through ``run_sweep(engine="batch")`` on a 1-device mesh
    and again sharded over 8 virtual CPU devices, in one subprocess --
    reporting steady-state cells/s both ways, the speedup, per-bucket
    ``compile_s``, and the bit-identity of per-cell results across meshes
    (parity is the hard invariant; the speedup is hardware-honest and
    reflects however many physical cores back the virtual devices).
    Scale half: one 10,000-host / 100,000-VM-slot cell under cpc+static,
    completing end-to-end through the same path."""
    from benchmarks.sweep_sharded import run_probe
    grid = run_probe(8, "--mode", "grid", "--cells", "256",
                          "--hosts", "10", "--duration", "600",
                          "--tick", "10")
    scale = run_probe(8, "--mode", "scale", "--hosts", "10000",
                           "--duration", "600", "--tick", "30")
    ARTIFACT["sweep_scale_sharded"] = {
        "n_cells": grid["n_cells"],
        "n_hosts": grid["n_hosts"],
        "n_devices": grid["sharded"]["n_devices"],
        "cells_per_s_single": grid["single"]["cells_per_s"],
        "cells_per_s_sharded": grid["sharded"]["cells_per_s"],
        "speedup_vs_single_device": grid["speedup"],
        "parity_bit_identical": grid["parity"],
        "compile_s_single": grid["single"]["compile_s"],
        "compile_s_sharded": grid["sharded"]["compile_s"],
        "datacenter_cell": {
            "n_hosts": scale["n_hosts"],
            "n_vm_slots": scale["n_vm_slots"],
            "ticks": scale["ticks"],
            "steady_s": scale["steady_s"],
            "compile_s": scale["compile_s"],
        },
    }
    return (f"{grid['n_cells']}cells@{grid['n_hosts']}h:"
            f"1dev:{grid['single']['cells_per_s']:.1f}cells/s"
            f";8dev:{grid['sharded']['cells_per_s']:.1f}cells/s"
            f";speedup:{grid['speedup']:.2f}x"
            f";parity:{'exact' if grid['parity'] else 'FAIL'}"
            f";10k-host:{scale['steady_s']:.1f}s"
            f"/{scale['ticks']}ticks"
            f";compile:{grid['sharded']['compile_s']:.1f}s")


def roofline_summary():
    pats = os.path.join(os.path.dirname(__file__), "..", "results",
                        "dryrun", "*.json")
    cells = []
    for p in sorted(glob.glob(pats)):
        with open(p) as f:
            d = json.load(f)
        if d.get("ok"):
            cells.append(d)
    if not cells:
        return "no-dryrun-results(run repro.launch.dryrun first)"
    by_dom = {}
    for c in cells:
        by_dom.setdefault(c["roofline"]["dominant"], []).append(c)
    return (f"{len(cells)}cells;" + ";".join(
        f"{k}:{len(v)}" for k, v in sorted(by_dom.items())))


def budget_service():
    """Hierarchical-budget control plane: replay latency + parity.

    Replays a mixed synthetic event feed (headroom/admission queries,
    demand updates, power churn, node-limit changes) through
    ``repro.runtime.budget_service.BudgetService`` over a two-row budget
    tree, and runs the ``row_contention`` tree sweep slice batch vs
    vector.  Reports p50/p99 per-event latency and both parity checks;
    ``benchmarks.check_regression`` gates the same measurement in CI."""
    from benchmarks.check_regression import measure_budget_service
    m = measure_budget_service()
    ARTIFACT["budget_service"] = m
    return (f"{m['n_events']}events@{m['n_hosts']}h:"
            f"p50:{m['p50_us']:.0f}us;p99:{m['p99_us']:.0f}us;"
            f"decisions:{m['n_decisions']};"
            f"headroom_parity:{m['headroom_parity_max_w']:.1e};"
            f"row_contention:"
            f"{'exact' if m['row_contention_parity'] else 'FAIL'}")


def kernel_microbenches():
    from benchmarks.kernel_bench import BENCHES as KB
    parts = []
    for name, fn in KB:
        us, derived = fn()
        parts.append(f"{name.replace('kernel_', '')}:{us:.0f}us")
    return ";".join(parts) + ";(interpret-mode)"


BENCHES = [
    ("table2_deployments", table2_deployments, False),
    ("table3_rebalancing", table3_rebalancing, False),
    ("table4_standby", table4_standby, False),
    ("table5_flexible", table5_flexible, True),
    ("powercap_latency", powercap_latency, False),
    ("sweep_scale", sweep_scale, True),
    ("sweep_grid", sweep_grid, True),
    ("sweep_grid_dpm", sweep_grid_dpm, True),
    ("sweep_grid_rules", sweep_grid_rules, True),
    ("sweep_grid_timed", sweep_grid_timed, True),
    ("sweep_e2e", sweep_e2e, True),
    ("sweep_scale_sharded", sweep_scale_sharded, True),
    ("budget_service", budget_service, True),
    ("kernel_microbenches", kernel_microbenches, False),
    ("roofline_summary", roofline_summary, False),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-slow", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="write sweep throughput to BENCH_sweep.json")
    ap.add_argument("--only", action="append", default=None, metavar="NAME",
                    help="run only the named bench (repeatable)")
    args, _ = ap.parse_known_args()
    if args.only:
        unknown = set(args.only) - {name for name, _, _ in BENCHES}
        if unknown:
            ap.error(f"unknown bench(es): {sorted(unknown)}")
    # Persistent XLA compile cache: re-running the harness on unchanged
    # grid shapes pays trace + load instead of full recompiles (the rules
    # grid alone costs ~14 s of XLA time per cold process).
    from repro.sim.sweep import enable_compilation_cache
    print(f"# jax compilation cache: {enable_compilation_cache()}",
          flush=True)
    print("name,us_per_call,derived")
    for name, fn, slow in BENCHES:
        if args.only is not None and name not in args.only:
            continue
        if slow and args.skip_slow:
            print(f"{name},skipped,--skip-slow")
            continue
        us, derived = _timed(fn)
        print(f"{name},{us:.0f},{derived}", flush=True)
    if args.json:
        if not ARTIFACT:
            # The sweep benches populate ARTIFACT and are both slow: with
            # --skip-slow there is nothing to record, and clobbering the
            # committed perf trajectory with '{}' would erase it.
            print("BENCH_sweep.json not written: sweep benches were skipped",
                  flush=True)
            return
        path = os.path.normpath(os.path.join(os.path.dirname(__file__),
                                             "..", "BENCH_sweep.json"))
        # Merge over the committed file: the smoke baselines (and any
        # full-size entry a --skip-slow run didn't re-measure) survive, so
        # a nightly `git diff` shows real drift, not dropped sections.
        data = {}
        if os.path.exists(path):
            with open(path) as f:
                data = json.load(f)
        data.update(ARTIFACT)
        with open(path, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
        print(f"wrote {path}", flush=True)


if __name__ == "__main__":
    main()
