"""Run the batched sweep engine once on a TPU and check what comes out.

    python chip_smoke.py                # one chip: cap-only and DPM grids
    python chip_smoke.py --four-chips   # the DPM grid sharded over 4 chips

One chip (the default): a datacenter-scale grid of DRS clusters goes
through the normal entry point, ``run_sweep(specs, engine="batch",
n_devices=1)``, once per regime -- cap-only management and DPM capacity
churn.  Each grid is the ``scenario_families`` spike x host-mix variants at
64 hosts per cluster (the vSphere 6.x per-cluster host maximum), 10 VMs per
host, every policy in ``POLICIES``, repeated over seeds until it holds at
least 10,000 simulated hosts (the scale of the ``sweep_scale_sharded``
10k-host cell), over one simulated hour: 360 ticks of 10 s, 12 DRS
invocations at 300 s.  Two cells per regime (``cpc`` and ``static`` of the
first spec) are run again on the object ``Simulator``, the plain NumPy
reference on the host, and compared: action counts exactly, energy and
CPU payload within :data:`RTOL`.  The script fails if a ``cpc`` cell makes
no cap change, or if the DPM grid shows no power event or no vMotion: a
cell where the manager does nothing checks nothing.

``--four-chips`` runs only the sharded path: the DPM grid with
``n_devices=4`` and again with ``n_devices=1`` in this process.  Each of
the four chips must report a non-zero peak of device memory, so no chip
was left out.  On the CPU, sharded and single-device results are
bit-identical (``tests/test_sharded_parity.py``).  On the TPU they are
not, and sharding is not the cause: float64 is emulated there, and a
different compiled program may round a reduction differently.  On a TPU
v5e, one chip gives different ``cpu_payload_mhz_s`` for the first 42
cells when they run as a 42-cell grid instead of inside the 168-cell
grid (4 of 42 cells, up to 1.2e-14 relative); 4 chips against 1 differ
the same way in 14 of 168 cells (up to 2.4e-14), with action counts and
energy equal.  So the two runs are held to the contract against the
reference: action counts exactly, energy and payload within
:data:`RTOL`.  The script prints how many cells are bit-identical and
every cell that is not.

Everything runs in this one process, which holds the chip(s); no child
process is started.  The persistent compilation cache is on from the start
(``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` at the
checkout root).  With no TPU the script prints why and exits 1.  Every line
but the last is a report; the last line is one JSON object with ``ok`` and
the device as JAX reports it, printed only when every check passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

HOSTS_PER_CLUSTER = 64        # vSphere 6.x configuration maximum per cluster
VMS_PER_HOST = 10             # SweepSpec's default density; the manager acts
MIN_HOSTS = 10_000            # simulated hosts per grid
DURATION_S = 3600.0           # 360 ticks of 10 s, 12 DRS invocations

#: Relative tolerance on energy and CPU payload against the object
#: ``Simulator``: the repo's CPU parity contract (``tests/test_sweep.py``,
#: ``tests/test_batch_parity.py``).  The chip computes float64 by emulation,
#: so this is what the smoke test holds it to until ROADMAP queue 1, item 3
#: sets a precision contract.  Action counts must match exactly.
RTOL = 1e-9

REGIMES = (("cap-only", "none"), ("dpm", "dpm"))
COUNTS = ("cap_changes", "vmotions", "power_ons", "power_offs")
FLOATS = ("energy_j", "cpu_payload_mhz_s")


def grid(churn: str, min_hosts: int = MIN_HOSTS,
         duration_s: float = DURATION_S) -> list:
    """The smoke grid of one regime: spike x host-mix variants at 64 hosts,
    repeated over seeds until ``specs x POLICIES`` holds ``min_hosts``."""
    from repro.sim.experiments import POLICIES
    from repro.sim.sweep import scenario_families

    base = scenario_families(sizes=(HOSTS_PER_CLUSTER,), churns=(churn,),
                             duration_s=duration_s)
    per_seed = len(base) * len(POLICIES) * HOSTS_PER_CLUSTER
    return [dataclasses.replace(s, name=f"{s.name}_s{seed}", seed=seed,
                                vms_per_host=VMS_PER_HOST)
            for seed in range(math.ceil(min_hosts / per_seed))
            for s in base]


def run_grid(specs: list, n_devices: int) -> tuple[dict, float, list]:
    """``run_sweep`` on the batched engine; returns (results, wall seconds,
    per-bucket records of ``LAST_BATCH_INFO``)."""
    from repro.sim import sweep

    t0 = time.perf_counter()
    res = sweep.run_sweep(specs, engine="batch", n_devices=n_devices)
    wall = time.perf_counter() - t0
    return res, wall, [dict(b) for b in sweep.LAST_BATCH_INFO]


def check_grid(label: str, churn: str, res: dict) -> list[str]:
    """Finite results for every cell, and a manager that acts."""
    failures = []
    cells = [r for per in res.values() for r in per.values()]
    for r in cells:
        for f in FLOATS:
            v = getattr(r, f)
            if not (math.isfinite(v) and v > 0.0):
                failures.append(f"{label}: {r.spec.name}/{r.policy} {f}={v}")
    idle = [r.spec.name for r in cells
            if r.policy == "cpc" and r.cap_changes == 0]
    if idle:
        failures.append(f"{label}: {len(idle)} cpc cells made no cap change, "
                        f"e.g. {idle[:3]}")
    totals = {c: sum(getattr(r, c) for r in cells) for c in COUNTS}
    print(f"[{label}] {len(cells)} cells, totals {totals}")
    if churn == "dpm":
        if totals["power_ons"] + totals["power_offs"] == 0:
            failures.append(f"{label}: no power event in the DPM grid")
        if totals["vmotions"] == 0:
            failures.append(f"{label}: no vMotion in the DPM grid")
    return failures


def compare(tag: str, got, want) -> tuple[dict, dict, list[str]]:
    """One cell against another: ``(counts, relative deltas, failures)``,
    with counts as ``(got, want)`` pairs, held to exact counts and
    :data:`RTOL`."""
    counts = {c: (getattr(got, c), getattr(want, c)) for c in COUNTS}
    rel = {f: float(abs(getattr(got, f) - getattr(want, f))
                    / abs(getattr(want, f))) for f in FLOATS}
    failures = [f"{tag} {c} {g} != {w}"
                for c, (g, w) in counts.items() if g != w]
    failures += [f"{tag} {f} off by {d!r} > {RTOL}"
                 for f, d in rel.items() if not d <= RTOL]
    return counts, rel, failures


def check_reference(label: str, spec, res: dict) -> list[str]:
    """``cpc`` and ``static`` of ``spec`` against the object Simulator."""
    from repro.sim.sweep import run_cell

    failures = []
    for p in ("cpc", "static"):
        t0 = time.perf_counter()
        want = run_cell(spec, p, engine="legacy")
        ref_s = time.perf_counter() - t0
        counts, rel, bad = compare(f"{label}: {spec.name}/{p}",
                                   res[spec.name][p], want)
        print(f"[{label}] parity {spec.name}/{p} vs object Simulator "
              f"({ref_s!r} s): counts (chip, reference) {counts}, "
              f"relative deltas {rel}")
        failures += bad
    return failures


def report_buckets(label: str, wall: float, buckets: list) -> None:
    for b in buckets:
        print(f"[{label}] bucket {b['bucket']} cells={b['n_cells']} "
              f"devices={b['n_devices']} compile_s={b['compile_s']!r} "
              f"pack_s={b['pack_s']!r} run_s={b['run_s']!r}")
    print(f"[{label}] run_sweep wall {wall!r} s")


def one_chip() -> list[str]:
    failures = []
    for label, churn in REGIMES:
        specs = grid(churn)
        print(f"[{label}] {len(specs)} specs x POLICIES at "
              f"{HOSTS_PER_CLUSTER} hosts, {VMS_PER_HOST} VMs/host, "
              f"{DURATION_S:.0f} s")
        res, wall, buckets = run_grid(specs, n_devices=1)
        report_buckets(label, wall, buckets)
        failures += check_grid(label, churn, res)
        failures += check_reference(label, specs[0], res)
    return failures


def four_chips(devices) -> list[str]:
    label = "dpm 4 chips"
    specs = grid("dpm")
    res4, wall4, buckets4 = run_grid(specs, n_devices=4)
    report_buckets(label, wall4, buckets4)
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices[:4]]
    print(f"[{label}] peak_bytes_in_use per device {peaks}")
    failures = check_grid(label, "dpm", res4)
    if any(b["n_devices"] != 4 for b in buckets4):
        failures.append(f"{label}: a bucket ran on fewer than 4 devices")
    if not all(p > 0 for p in peaks):
        failures.append(f"{label}: a device holds no shard: peaks {peaks}")

    res1, wall1, buckets1 = run_grid(specs, n_devices=1)
    report_buckets("dpm 1 chip", wall1, buckets1)
    n_cells = n_same = 0
    for name, per in res1.items():
        for p, want in per.items():
            counts, rel, bad = compare(f"{label}: {name}/{p} vs 1 chip:",
                                       res4[name][p], want)
            failures += bad
            n_cells += 1
            if any(g != w for g, w in counts.values()) or any(rel.values()):
                print(f"[{label}] {name}/{p} counts (4 chips, 1 chip) "
                      f"{counts}, relative deltas {rel}")
            else:
                n_same += 1
    print(f"[{label}] {n_same} of {n_cells} cells bit-identical to 1 chip")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the DPM grid sharded over 4 chips, "
                         "against 1 chip")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX's first device is {devices[0].platform!r}; "
              "this smoke test runs on the chip only", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"--four-chips needs 4 devices, JAX sees {len(devices)}",
              file=sys.stderr)
        return 1

    sys.path.insert(0, SRC)
    from repro.sim.sweep import enable_compilation_cache

    print(f"compilation cache: {enable_compilation_cache()}")
    print(f"device: {devices[0].device_kind} x {len(devices)}, "
          f"jax {jax.__version__}")
    failures = four_chips(devices) if args.four_chips else one_chip()
    print(f"peak_bytes_in_use: "
          f"{[d.memory_stats()['peak_bytes_in_use'] for d in devices[:want]]}")
    if failures:
        print("FAILED:\n  " + "\n  ".join(failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
