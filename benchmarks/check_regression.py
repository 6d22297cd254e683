"""Benchmark smoke: sweep-throughput regression gate for CI.

Runs a fixed *tiny* scenario grid -- a cap-only slice and a capacity-churn
slice -- through both the batched (jitted) and sequential (vector) sweep
engines, and gates on the batched/sequential **speedup**.  Speedup is the
machine-portable throughput metric: both sides execute in the same process
on the same hardware, so a CI runner's absolute cells/s cancels out, while
a regression in the compiled program (an accidental host-sync, a carry that
stopped aliasing, a kernel falling off the fused path) shows up directly.

Also gates the overlapped sweep pipeline (``sweep_e2e``): the cap-only
smoke grid clocked end-to-end -- scenario construction, TraceBank packing,
AOT dispatch, harvest -- against its steady-state device wall.  The gated
``e2e_ratio`` (e2e / steady cells/s) is machine-portable for the same
reason speedup is, and drops when host-side work creeps back onto the
critical path.

Also gates the sharded sweep engine (``sweep_scale_sharded``): a tiny grid
runs on a 1-device and an 8-virtual-device ``("cells",)`` mesh in a
subprocess; per-cell results must be bit-identical across the two meshes
(hard gate), and the sharded/single speedup must hold its committed floor
whenever the runner has at least as many cores as forced virtual devices
(oversubscribed runners skip the floor -- their throughput is scheduler
noise, not a property of the compiled program).

Also gates the fused Pallas allocation kernel (``kernel_waterfill``): the
CI runner has no TPU, so interpret-mode wall time is correctness-grade
noise and is recorded informationally only -- the gate is *parity*, the
kernel's actual contract: bitwise-identical float64 output against the lax
executor on a fixed problem.  Any drift in the fused kernel (a masking
change, a reduction reorder, an accidental f32 cast) fails the gate even
when every timing looks fine.

The committed baseline lives in ``BENCH_sweep.json`` under ``"smoke"``;
the gate fails when a grid's speedup drops more than ``--tolerance``
(default 30%) below it.  The baseline should be refreshed with
``--update-baseline`` on low-core hardware: extra cores help the jitted
batched side more than the single-threaded NumPy side, so a baseline
from a small machine is a conservative floor on bigger CI runners.  The
full-size headline numbers (``sweep_grid`` / ``sweep_grid_dpm``) are
tracked separately by ``benchmarks/run.py --json``.

Usage:
  PYTHONPATH=src python -m benchmarks.check_regression              # gate
  PYTHONPATH=src python -m benchmarks.check_regression --update-baseline
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BASELINE_PATH = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "BENCH_sweep.json"))


def _grids():
    from repro.sim.sweep import scenario_families
    return {
        "sweep_grid": scenario_families(
            sizes=(20,), budgets_per_host_w=(250.0,),
            spikes=("burst", "prime"), heterogeneous=(False, True),
            churns=("none",), duration_s=600.0, tick_s=10.0),
        # 1500 s so the DPM valley spans the stability window and the
        # cells actually power hosts off/on (see sweep_grid_dpm).
        "sweep_grid_dpm": scenario_families(
            sizes=(20,), budgets_per_host_w=(250.0,),
            spikes=("burst",), heterogeneous=(False, True),
            churns=("dpm", "failure"), duration_s=1500.0, tick_s=30.0),
        # Migration layer live: constraint-correction bursts and
        # cap-blocked (Fig. 1a) corrections with the hill-climb balancer
        # (see sweep_grid_rules).
        "sweep_grid_rules": scenario_families(
            sizes=(20,), budgets_per_host_w=(250.0,),
            spikes=("burst",), heterogeneous=(False, True),
            rules=("violation_burst", "cap_blocked"),
            duration_s=600.0, tick_s=10.0),
        # Timed-migration execution model: multi-tick copy windows in the
        # scan-state in-flight table, slot/bandwidth-gated launches, both
        # endpoints charged -- cells that used to fall off the batched
        # engine (see sweep_grid_timed).  10 s ticks keep transfers
        # multi-tick; 900 s spans three DRS invocations.
        "sweep_grid_timed": scenario_families(
            sizes=(20,), budgets_per_host_w=(250.0,),
            spikes=("burst",), heterogeneous=(False, True),
            churns=("timed_churn", "failure_cascade"),
            duration_s=900.0, tick_s=10.0),
    }


def measure() -> dict:
    from repro.sim.sweep import run_cell, run_sweep_batched
    policies = ("cpc", "static")
    out = {}
    for name, specs in _grids().items():
        run_sweep_batched(specs, policies=policies)      # jit compile
        res = run_sweep_batched(specs, policies=policies)
        batch_wall = sum(r.wall_s for by_p in res.values()
                         for r in by_p.values())
        n_cells = len(specs) * len(policies)
        seq_wall, seq_cells = 0.0, 0
        for spec in specs[:2]:
            for p in policies:
                seq_wall += run_cell(spec, p, engine="vector").wall_s
                seq_cells += 1
        out[name] = {
            "n_cells": n_cells,
            "n_hosts": specs[0].n_hosts,
            "cells_per_s_batched": n_cells / batch_wall,
            "cells_per_s_sequential": seq_cells / seq_wall,
            "speedup": (n_cells / batch_wall) / (seq_cells / seq_wall),
        }
    return out


def measure_e2e() -> dict:
    """``sweep_e2e`` smoke: pipeline efficiency end-to-end.

    Runs the cap-only smoke grid through ``run_sweep_batched`` twice (the
    first call warms the AOT executables) and clocks the second from the
    ``SweepSpec`` list to merged results.  The gated metric is the
    **e2e ratio** -- e2e cells/s over steady-state (device-wall) cells/s.
    Like speedup it is machine-portable: both walls come from the same
    process on the same hardware, so a regression in the overlapped
    pipeline (packing back on the critical path, a host sync between
    dispatch and harvest, scenario construction reverting to per-VM
    factories) lowers the ratio on any runner.
    """
    import time

    from repro.sim.sweep import LAST_BATCH_INFO, run_sweep_batched
    specs = _grids()["sweep_grid"]
    policies = ("cpc", "static")
    n_cells = len(specs) * len(policies)
    run_sweep_batched(specs, policies=policies)      # warm AOT executables
    t0 = time.perf_counter()
    run_sweep_batched(specs, policies=policies)
    e2e_wall = time.perf_counter() - t0
    run_s = sum(b["run_s"] for b in LAST_BATCH_INFO)
    return {
        "n_cells": n_cells,
        "n_hosts": specs[0].n_hosts,
        "cells_per_s_e2e": n_cells / e2e_wall,
        "cells_per_s_steady": n_cells / run_s,
        "e2e_ratio": run_s / e2e_wall,
    }


def measure_sharded() -> dict:
    """``sweep_scale_sharded`` smoke: the sharded sweep engine on 8 virtual
    CPU devices, in a subprocess (the cells mesh needs the forced device
    count set before jax initializes).

    Two gates ride on this entry: per-cell results across the 1-device and
    8-device meshes must be **bit-identical** (the sharding contract --
    cells are embarrassingly parallel, so the compiled arithmetic is the
    same program either way), and the sharded/single **speedup** must stay
    within tolerance of the committed baseline.  A baseline measured on
    low-core hardware is a conservative floor: real cores only help the
    sharded side.
    """
    from benchmarks.sweep_sharded import run_probe

    g = run_probe(8, "--mode", "grid", "--cells", "16", "--hosts", "6",
                  "--duration", "300", "--tick", "30")
    n_devices = g["sharded"]["n_devices"]
    return {
        "n_cells": g["n_cells"],
        "n_hosts": g["n_hosts"],
        "n_devices": n_devices,
        "cells_per_s_single": g["single"]["cells_per_s"],
        "cells_per_s_sharded": g["sharded"]["cells_per_s"],
        "speedup": g["speedup"],
        "parity_bit_identical": bool(g["parity"]),
        # Whether the speedup floor is meaningful on THIS runner: with
        # fewer cores than forced virtual devices the sharded side is pure
        # oversubscription, so the floor is waived (parity still gates).
        "enforced": n_devices <= (os.cpu_count() or 1),
    }


def measure_kernel() -> dict:
    """``kernel_waterfill``: parity-gated, timing-informational.

    Runs the fused Pallas dense waterfill and the dispatch-free lax
    reference on the same fixed float64 problem (interpret mode off-TPU)
    and records the max absolute difference -- the gate requires exactly
    0.0, the bit-identity the differential test harness locks down.
    """
    import time

    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.kernels.powercap import ops, ref

    rng = np.random.default_rng(0)
    s, h, j = 4, 16, 8
    floors = rng.uniform(0.0, 300.0, (s, h, j))
    ceils = floors + rng.uniform(0.0, 500.0, (s, h, j))
    weights = rng.uniform(0.1, 10.0, (s, h, j))
    active = rng.random((s, h, j)) < 0.8
    floors = np.where(active, floors, 0.0)
    ceils = np.where(active, ceils, 0.0)
    capacity = rng.uniform(0.0, 1.2, (s, h)) * np.maximum(
        ceils.sum(axis=-1), 1.0)
    with jax.enable_x64(True):
        args = tuple(jnp.asarray(a) for a in (capacity, floors, ceils,
                                              weights))
        act = jnp.asarray(active)
        got = ops.pallas_waterfill_dense(*args, active=act)
        want = ref.lax_waterfill_dense(*args, active=act)
        got.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(3):
            ops.pallas_waterfill_dense(*args,
                                       active=act).block_until_ready()
        us = (time.perf_counter() - t0) / 3 * 1e6
        return {
            "bit_identical": bool(jnp.all(got == want)),
            "max_abs_diff_vs_lax": float(jnp.abs(got - want).max()),
            "us_per_call_interpret": us,
        }


def measure_budget_service() -> dict:
    """``budget_service``: the live headroom/admission service and the
    hierarchical-budget sweep family, parity-gated with a generous
    latency bound.

    Three things ride on this entry: (1) the service's headroom answers
    must equal brute-force recomputation exactly on the post-replay state
    (the control plane's core contract); (2) the ``row_contention``
    budget-tree sweep slice must replay identically batch vs vector
    (exact cap-change counts, 1e-9 payload/energy); (3) replay latency
    percentiles are recorded, gated only against a 10x-the-baseline
    ceiling -- absolute microseconds are runner noise, an order of
    magnitude is an accidental O(n^2) or a jit on the hot path.
    """
    import numpy as np

    from repro.core.budget_tree import BudgetTree
    from repro.runtime import budget_service as bsvc
    from repro.sim.sweep import row_contention_specs, run_sweep

    n_hosts, n_events = 50, 4000
    budget = 250.0 * n_hosts
    tree = BudgetTree.two_rows(budget, n_hosts, row0_limit=0.45 * budget)
    hosts = [f"host{i}" for i in range(n_hosts)]
    on = np.ones(n_hosts, dtype=bool)
    caps0 = tree.project(np.full(n_hosts, 250.0), on,
                         floors=np.zeros(n_hosts))
    svc = bsvc.BudgetService(tree, hosts, caps0, on)
    rep = svc.replay(bsvc.synthetic_feed(tree, n_events=n_events, seed=0))
    parity = max(abs(svc.headroom(h) - svc.brute_force_headroom(h))
                 for h in hosts)

    # 600 s reaches past the burst onset, so the cpc cell really changes
    # caps under the binding row and the parity bit is non-trivial.
    specs = row_contention_specs(sizes=(10,), duration_s=600.0)
    policies = ("cpc", "static")
    vec = run_sweep(specs, policies=policies, engine="vector")
    bat = run_sweep(specs, policies=policies, engine="batch")
    sweep_active = any(vec[s]["cpc"].cap_changes > 0 for s in vec)
    sweep_exact = sweep_active and all(
        vec[s][p].cap_changes == bat[s][p].cap_changes
        and abs(vec[s][p].cpu_payload_mhz_s - bat[s][p].cpu_payload_mhz_s)
        <= 1e-9 * abs(vec[s][p].cpu_payload_mhz_s)
        and abs(vec[s][p].energy_j - bat[s][p].energy_j)
        <= 1e-9 * abs(vec[s][p].energy_j)
        for s in vec for p in vec[s])
    return {
        "n_hosts": n_hosts,
        "n_events": rep.n_events,
        "n_decisions": rep.n_decisions,
        "n_errors": rep.n_errors,
        "p50_us": rep.p50_us,
        "p99_us": rep.p99_us,
        "headroom_parity_max_w": float(parity),
        "row_contention_parity": bool(sweep_exact),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--update-baseline", action="store_true",
                    help="write the measured smoke speedups into "
                         "BENCH_sweep.json instead of gating")
    ap.add_argument("--tolerance", type=float, default=0.30,
                    help="allowed fractional speedup regression")
    args = ap.parse_args()

    measured = measure()
    for name, m in measured.items():
        print(f"{name}: {m['n_cells']}cells@{m['n_hosts']}h "
              f"batched {m['cells_per_s_batched']:.1f} cells/s, "
              f"sequential {m['cells_per_s_sequential']:.1f} cells/s, "
              f"speedup {m['speedup']:.2f}x", flush=True)
    measured["sweep_e2e"] = me = measure_e2e()
    print(f"sweep_e2e: {me['n_cells']}cells@{me['n_hosts']}h "
          f"e2e {me['cells_per_s_e2e']:.1f} cells/s, "
          f"steady {me['cells_per_s_steady']:.1f} cells/s, "
          f"ratio {me['e2e_ratio']:.2f}", flush=True)
    measured["sweep_scale_sharded"] = ms = measure_sharded()
    print(f"sweep_scale_sharded: {ms['n_cells']}cells@{ms['n_hosts']}h "
          f"on {ms['n_devices']} virtual devices, "
          f"sharded {ms['cells_per_s_sharded']:.1f} cells/s vs single "
          f"{ms['cells_per_s_single']:.1f} cells/s "
          f"({ms['speedup']:.2f}x), parity "
          f"{'exact' if ms['parity_bit_identical'] else 'BROKEN'}",
          flush=True)
    measured["kernel_waterfill"] = mk = measure_kernel()
    print(f"kernel_waterfill: max_abs_diff vs lax "
          f"{mk['max_abs_diff_vs_lax']:.1e}, "
          f"{mk['us_per_call_interpret']:.0f}us/call (interpret mode, "
          f"informational)", flush=True)
    measured["budget_service"] = mb = measure_budget_service()
    print(f"budget_service: {mb['n_events']}events@{mb['n_hosts']}h "
          f"p50 {mb['p50_us']:.0f}us p99 {mb['p99_us']:.0f}us, "
          f"headroom parity {mb['headroom_parity_max_w']:.1e}, "
          f"row_contention parity "
          f"{'exact' if mb['row_contention_parity'] else 'BROKEN'}",
          flush=True)

    with open(BASELINE_PATH) as f:
        bench = json.load(f)

    if args.update_baseline:
        bench["smoke"] = measured
        with open(BASELINE_PATH, "w") as f:
            json.dump(bench, f, indent=2, sort_keys=True)
        print(f"baseline updated in {BASELINE_PATH}")
        return 0

    baseline = bench.get("smoke")
    if not baseline:
        print("no committed smoke baseline in BENCH_sweep.json; run with "
              "--update-baseline and commit the result", file=sys.stderr)
        return 1
    failed = False
    for name, base in baseline.items():
        got = measured.get(name)
        if got is None:
            print(f"FAIL {name}: grid missing from this run",
                  file=sys.stderr)
            failed = True
            continue
        if "parity_bit_identical" in base:
            # Sharded engine: parity is the hard gate (bit-identical
            # per-cell results across mesh sizes).  The sharded/single
            # speedup floor catches collectives or resharding creeping
            # into the compiled program -- but it is only meaningful when
            # the virtual devices map onto real cores: on a runner with
            # fewer cores than forced devices the "sharded" side is pure
            # oversubscription and its throughput is scheduler noise, so
            # the floor is skipped (parity still gates).
            floor = base["speedup"] * (1.0 - args.tolerance)
            gate_speedup = got.get(
                "enforced", got["n_devices"] <= (os.cpu_count() or 1))
            ok = (got["parity_bit_identical"]
                  and (got["speedup"] >= floor or not gate_speedup))
            status = "ok" if ok else "FAIL"
            print(f"{status} {name}: parity "
                  f"{'exact' if got['parity_bit_identical'] else 'BROKEN'}"
                  f", speedup {got['speedup']:.2f}x vs baseline "
                  f"{base['speedup']:.2f}x (floor {floor:.2f}x, "
                  f"{'enforced' if gate_speedup else 'waived'})",
                  flush=True)
            if not gate_speedup:
                print(f"  floor waived: {got['n_devices']} forced virtual "
                      f"devices oversubscribe {os.cpu_count() or 1} "
                      f"physical core(s), so sharded throughput here is "
                      f"scheduler noise, not a property of the compiled "
                      f"program; the bit-identity parity gate still "
                      f"applies", flush=True)
            failed |= not ok
            continue
        if "headroom_parity_max_w" in base:
            # Budget service: parity is the hard gate (headroom answers
            # exactly equal brute force; the row_contention tree sweep
            # bit-stable batch vs vector).  Latency only fails at 10x the
            # committed baseline -- absolute microseconds are runner
            # noise, an order of magnitude is an algorithmic regression.
            ceil = max(base["p99_us"] * 10.0, 1000.0)
            ok = (got["headroom_parity_max_w"] == 0.0
                  and got["row_contention_parity"]
                  and got["p99_us"] <= ceil)
            status = "ok" if ok else "FAIL"
            print(f"{status} {name}: headroom parity "
                  f"{got['headroom_parity_max_w']:.1e} (gate: exactly 0), "
                  f"row_contention "
                  f"{'exact' if got['row_contention_parity'] else 'BROKEN'}"
                  f", p99 {got['p99_us']:.0f}us (ceiling {ceil:.0f}us)",
                  flush=True)
            failed |= not ok
            continue
        if "bit_identical" in base:
            # Parity gate: the fused kernel must stay bit-identical to the
            # lax executor; interpret-mode timing is never gated.
            ok = got["bit_identical"] and got["max_abs_diff_vs_lax"] == 0.0
            status = "ok" if ok else "FAIL"
            print(f"{status} {name}: pallas vs lax max_abs_diff "
                  f"{got['max_abs_diff_vs_lax']:.1e} (gate: exactly 0)",
                  flush=True)
            failed |= not ok
            continue
        if "e2e_ratio" in base:
            # Pipeline-efficiency gate: e2e over steady-state throughput.
            floor = base["e2e_ratio"] * (1.0 - args.tolerance)
            status = "ok" if got["e2e_ratio"] >= floor else "FAIL"
            print(f"{status} {name}: e2e ratio {got['e2e_ratio']:.2f} vs "
                  f"baseline {base['e2e_ratio']:.2f} (floor {floor:.2f})",
                  flush=True)
            failed |= got["e2e_ratio"] < floor
            continue
        floor = base["speedup"] * (1.0 - args.tolerance)
        status = "ok" if got["speedup"] >= floor else "FAIL"
        print(f"{status} {name}: speedup {got['speedup']:.2f}x vs baseline "
              f"{base['speedup']:.2f}x (floor {floor:.2f}x)",
              flush=True)
        failed |= got["speedup"] < floor
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
