"""One sweep cluster, built from its fields and run on the plain reference.

The cluster follows the sweep family the benchmark's grids name: ``n_hosts``
hosts (the configuration's Table I host, alternating with its small host in
heterogeneous clusters) whose budget is split pro rata by peak power
(``cpc``, ``static``) or spent on hosts at their peak until it runs out
(``statichigh``, the rest in standby with a zero cap); ``vms_per_host x
n_hosts`` VMs placed round robin over the powered-on hosts; each VM's
demand a step function of time drawn from ``RandomState(seed)``.  ``cpc``
changes caps, ``static`` and ``statichigh`` keep them; DPM is on when
``churn == "dpm"``; no placement rules and no migration search.
"""

from __future__ import annotations

import numpy as np

from bench.reference.cluster import VM, Cluster, HostSpec, simulate

COUNTS = ("cap_changes", "vmotions", "power_ons", "power_offs")
FLOATS = ("energy_j", "cpu_payload_mhz_s")
MEM_DEMAND_MB = 2 * 1024.0


def _host_specs(cell: dict, config: dict) -> list[HostSpec]:
    big = HostSpec(**config["hosts"]["paper_table1"])
    if not cell["heterogeneous"]:
        return [big] * cell["n_hosts"]
    small = HostSpec(**config["hosts"]["small"])
    return [big if i % 2 == 0 else small for i in range(cell["n_hosts"])]


def _segments(cell: dict, base: float, hot: bool, phase: float):
    """``((t0, cpu MHz), ...)`` of one VM's demand, and its period (None:
    aperiodic)."""
    d = cell["duration_s"]
    if cell["churn"] == "dpm":
        # Valley then burst: the middle third idles the cluster into DPM's
        # power-off band, the last third trips its power-on trigger.
        return ((0.0, base), (d / 3.0, 0.2 * base),
                (2.0 * d / 3.0, 2.2 * base + 1500.0)), None
    spike = cell["spike"]
    if spike == "flat" or (spike == "burst" and not hot):
        return ((0.0, base),), None
    if spike == "burst":
        # VMs on ~20% of hosts spike >2x in the middle third of the run.
        return ((0.0, base), (d / 3.0, 2.0 * base + 1200.0),
                (2.0 * d / 3.0, base)), None
    if spike == "step":
        return ((0.0, base), (d / 3.0, base / 3.0),
                (2.0 * d / 3.0, base)), None
    if spike == "prime":
        # Periodic off/prime/off window, its phase drawn per VM.
        off, prime = 0.3 * base, 2.2 * base
        if phase <= 0.0:
            return ((0.0, prime), ((phase + 0.4) * d, off)), d
        return ((0.0, off), (phase * d, prime),
                ((phase + 0.4) * d, off)), d
    raise ValueError(f"unknown spike pattern {spike!r}")


def _trace(segments, period):
    def at(t):
        if period is not None:
            t = t % period
        cpu = segments[0][1]
        for t0, c in segments:
            if t < t0:
                break
            cpu = c
        return cpu, MEM_DEMAND_MB
    return at


def build(cell: dict, config: dict):
    """``(cluster, traces)`` of one sweep cell."""
    if cell["churn"] not in ("none", "dpm"):
        raise ValueError(f"the reference runs churn 'none' or 'dpm', "
                         f"not {cell['churn']!r}")
    specs = _host_specs(cell, config)
    budget = cell["rack_budget_w"]
    if cell["policy"] == "statichigh":
        caps, on, spent = [], [], 0.0
        for s in specs:
            fits = spent + s.power_peak <= budget + 1e-9
            caps.append(s.power_peak if fits else 0.0)
            on.append(fits)
            spent += s.power_peak if fits else 0.0
    else:
        total_peak = sum(s.power_peak for s in specs)
        caps = [min(budget * s.power_peak / total_peak, s.power_peak)
                for s in specs]
        on = [True] * len(specs)
    on_hosts = [h for h in range(len(specs)) if on[h]]

    n_vms = cell["n_hosts"] * cell["vms_per_host"]
    rng = np.random.RandomState(cell["seed"])
    base = rng.uniform(600.0, 1400.0, size=n_vms).tolist()
    hot_host = (rng.rand(cell["n_hosts"]) < 0.2).tolist()
    phase = rng.uniform(0.0, 0.5, size=n_vms).tolist()
    vm_host = [on_hosts[v % len(on_hosts)] for v in range(n_vms)]
    traces = [_trace(*_segments(cell, base[v],
                                hot_host[v % len(on_hosts)], phase[v]))
              for v in range(n_vms)]
    vms = [VM() for _ in range(n_vms)]
    return Cluster(specs, caps, on, vms, vm_host, budget), traces


def run_cell(cell: dict, config: dict, dtype: str = "float64") -> dict:
    """The cluster's action counts, energy and CPU payload.  ``dtype`` is
    the precision of delivery and of the energy and payload sums."""
    cluster, traces = build(cell, config)
    dpm = None
    if cell["churn"] == "dpm":
        dpm = {k: config["dpm"][k] for k in (
            "high_util", "low_util", "target_util", "stable_window_s")}
    totals = simulate(
        cluster, traces, duration_s=cell["duration_s"],
        tick_s=cell["tick_s"], drs_period_s=cell["drs_period_s"],
        powercap=cell["policy"] == "cpc", dpm_params=dpm,
        num=float if dtype == "float64" else np.dtype(dtype).type)
    return ({k: getattr(totals, k) for k in COUNTS}
            | {k: float(getattr(totals, k)) for k in FLOATS})
