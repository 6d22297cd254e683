"""Sharded sweep scaling probe: one JSON object on stdout.

The ``("cells",)`` mesh can only span devices that exist when jax first
initializes, so multi-device CPU runs need
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set *before* the
first jax import.  The benchmark harness (``benchmarks/run.py``) and the
``sweep-sharded-smoke`` CI job therefore launch this module as a
subprocess with that flag and parse its stdout; it is equally runnable by
hand:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.sweep_sharded --mode grid

The probe is a CPU-only path.  On a TPU the parent already holds the chip
and a child would contend for it, so :func:`run_probe` refuses there; the
sharded path on real chips is ``python chip_smoke.py --four-chips``, which
runs everything in one process.

Modes:
  grid   -- an N-cell single-bucket grid run twice through
            ``run_sweep(engine="batch")``: once on 1 device, once sharded
            over every visible device.  Reports cells/s both ways, the
            speedup, per-bucket compile_s, and whether the per-cell
            results are bit-identical across the two meshes (they must
            be: cells are embarrassingly parallel, the compiled per-cell
            arithmetic is the same program either way).
  scale  -- the datacenter cell: ``--hosts`` hosts x 10 VMs/host (10k
            hosts => 100k VM slots) under cpc+static, sharded over (at
            most) 2 devices since the grid is 2 cells.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))


def run_probe(n_devices: int, *argv: str) -> dict:
    """Run this module in a child process with ``n_devices`` forced CPU
    devices (the cells mesh needs them to exist before jax initializes)
    and parse its JSON stdout.  Raises on a TPU backend instead of
    spawning a second process next to the one that holds the chip."""
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "the sharded sweep probe forces virtual CPU devices in a child "
            "process, which would contend for the TPU this process holds; "
            "run `python chip_smoke.py --four-chips` for the sharded path "
            "on chips")
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.sweep_sharded", *argv],
        capture_output=True, text=True, env=env, cwd=_ROOT)
    if proc.returncode != 0:
        raise RuntimeError(f"sweep_sharded probe failed:\n{proc.stderr}")
    return json.loads(proc.stdout)


def _fingerprint(res) -> list:
    """Exact per-cell results in spec x policy order, JSON-stable."""
    out = []
    for name in res:
        for p, r in res[name].items():
            out.append([name, p, int(r.cap_changes), int(r.vmotions),
                        int(r.power_ons), int(r.power_offs),
                        float(r.energy_j).hex(),
                        float(r.cpu_payload_mhz_s).hex()])
    return out


def _grid_specs(n_cells: int, n_hosts: int, duration_s: float,
                tick_s: float):
    from repro.sim.sweep import scenario_families
    n_specs = n_cells // 2
    # 8 specs per budget point: 4 spike families x 2 host mixes.
    budgets = [200.0 + 10.0 * i for i in range(max(1, -(-n_specs // 8)))]
    specs = scenario_families(
        sizes=(n_hosts,), budgets_per_host_w=budgets,
        spikes=("flat", "burst", "step", "prime"),
        heterogeneous=(False, True), duration_s=duration_s, tick_s=tick_s)
    if len(specs) < n_specs:
        raise SystemExit(f"grid tops out at {2 * len(specs)} cells")
    return specs[:n_specs]


def _run(specs, policies, n_devices):
    from repro.sim import sweep as sw
    t0 = time.perf_counter()
    res = sw.run_sweep(specs, policies=policies, engine="batch",
                       n_devices=n_devices)
    first_s = time.perf_counter() - t0
    buckets = [dict(b) for b in sw.LAST_BATCH_INFO]
    t0 = time.perf_counter()
    res = sw.run_sweep(specs, policies=policies, engine="batch",
                       n_devices=n_devices)
    steady_s = time.perf_counter() - t0
    n_cells = len(specs) * len(policies)
    return res, {
        "n_cells": n_cells,
        "n_devices": max(b["n_devices"] for b in buckets),
        "first_s": first_s,
        "steady_s": steady_s,
        "cells_per_s": n_cells / steady_s,
        "compile_s": sum(b["compile_s"] for b in buckets),
        "buckets": buckets,
    }


def measure_grid(n_cells: int, n_hosts: int, duration_s: float,
                 tick_s: float) -> dict:
    import jax
    specs = _grid_specs(n_cells, n_hosts, duration_s, tick_s)
    policies = ("cpc", "static")
    res1, single = _run(specs, policies, n_devices=1)
    resn, sharded = _run(specs, policies, n_devices=None)
    return {
        "n_cells": n_cells,
        "n_hosts": n_hosts,
        "visible_devices": len(jax.devices()),
        "single": single,
        "sharded": sharded,
        "speedup": sharded["cells_per_s"] / single["cells_per_s"],
        "parity": _fingerprint(res1) == _fingerprint(resn),
    }


def measure_scale(n_hosts: int, duration_s: float, tick_s: float) -> dict:
    from repro.sim.sweep import SweepSpec, run_sweep
    # 230 W/host is the paper's constrained-budget regime: DRS ticks must
    # actually redistribute caps, so the datacenter cell exercises the full
    # pipeline rather than coasting on headroom.
    spec = SweepSpec(name=f"h{n_hosts}_burst", n_hosts=n_hosts,
                     spike="burst", rack_budget_w=230.0 * n_hosts,
                     duration_s=duration_s, tick_s=tick_s)
    res, stats = _run([spec], ("cpc", "static"), n_devices=None)
    r = res[spec.name]["cpc"]
    stats.update(n_hosts=n_hosts, n_vm_slots=n_hosts * 10, ticks=r.ticks,
                 ticks_per_s=r.ticks_per_s,
                 cap_changes=int(r.cap_changes))
    return stats


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("grid", "scale"), default="grid")
    ap.add_argument("--cells", type=int, default=256)
    ap.add_argument("--hosts", type=int, default=10)
    ap.add_argument("--duration", type=float, default=600.0)
    ap.add_argument("--tick", type=float, default=10.0)
    args = ap.parse_args()
    if args.mode == "grid":
        out = measure_grid(args.cells, args.hosts, args.duration, args.tick)
    else:
        out = measure_scale(args.hosts, args.duration, args.tick)
    json.dump(out, sys.stdout)
    sys.stdout.write("\n")


if __name__ == "__main__":
    main()
