"""Cells as data: the benchmark's entries, configurations and traffic mixes,
and the generator that turns one (configuration, mix, seed, grid index)
into a grid of DRS clusters.

A grid is a list of plain dicts, each the fields of one sweep cell (a
``SweepSpec``) plus its ``policy``.  The program under test and the plain
reference each build their own spec objects from these dicts, so neither
sees anything the other made.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

#: Seeds of the clusters of one grid are drawn below this bound: the
#: scenario builder seeds NumPy's legacy ``RandomState`` with them.
SEED_BOUND = 2**31


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str):
    """``(entry, config, traffic)`` of the cell ``workload``: its entry in
    ``BENCHMARK.json`` and the files its configuration and mix name."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"cells: {sorted(entries)}")
    return load_entry(entries[workload])


def load_entry(entry: dict):
    """``(entry, config, traffic)``: a cell's entry with the files its
    configuration and mix name."""
    config = load_json(BENCH_DIR / "configs" / f"{entry['config']}.json")
    traffic = load_json(BENCH_DIR / "traffic" / f"{entry['traffic']}.json")
    return entry, config, traffic


def families(traffic: dict) -> list[tuple[str, bool]]:
    """The mix's cluster families: spike pattern x host mix."""
    return [(spike, bool(het)) for spike in traffic["spikes"]
            for het in traffic["heterogeneous"]]


def seeds_per_grid(config: dict, traffic: dict) -> int:
    per_seed = len(families(traffic)) * len(traffic["policies"])
    n, rem = divmod(config["clusters_per_grid"], per_seed)
    if rem or n < 1:
        raise ValueError(
            f"clusters_per_grid {config['clusters_per_grid']} is not a "
            f"multiple of {per_seed} (families x policies of "
            f"{traffic['name']})")
    return n


def grid_seeds(seed: int, index: int, n: int) -> list[int]:
    """``n`` distinct cluster seeds of grid ``index`` of the run ``seed``:
    no grid repeats another's inputs, and one seed always gives the same
    grids."""
    rng = np.random.default_rng([int(seed) % 2**63, int(index)])
    return [int(s) for s in rng.choice(SEED_BOUND, size=n, replace=False)]


def grid(config: dict, traffic: dict, seed: int, index: int) -> list[dict]:
    """The clusters of one grid, in (cluster seed, family, policy) order."""
    if config["churn"] != traffic["churn"]:
        raise ValueError(
            f"configuration {config['name']} runs churn "
            f"{config['churn']!r}, mix {traffic['name']} drives "
            f"{traffic['churn']!r}")
    cells = []
    for s in grid_seeds(seed, index, seeds_per_grid(config, traffic)):
        for spike, het in families(traffic):
            name = (f"h{config['n_hosts']}_{spike}{'_het' if het else ''}"
                    f"_{config['churn']}_s{s}")
            for policy in traffic["policies"]:
                cells.append({
                    "name": name, "policy": policy,
                    "n_hosts": config["n_hosts"],
                    "vms_per_host": config["vms_per_host"],
                    "rack_budget_w": (config["budget_per_host_w"]
                                      * config["n_hosts"]),
                    "spike": spike, "heterogeneous": het,
                    "churn": config["churn"],
                    "duration_s": config["duration_s"],
                    "tick_s": config["tick_s"],
                    "drs_period_s": config["drs_period_s"],
                    "seed": s})
    return cells


def program_mismatches(config: dict) -> list[str]:
    """The settings of ``config`` that the program under test takes from
    its own defaults rather than from a grid's fields -- the host types and
    DPM's thresholds -- wherever those defaults differ from the file.  The
    reference reads them from the file, so a run refuses a mismatch."""
    from repro.core.power_model import PAPER_HOST
    from repro.drs.dpm import DPMConfig
    from repro.sim.sweep import SMALL_HOST
    held = [(f"hosts.{key}", spec, config["hosts"][key])
            for key, spec in (("paper_table1", PAPER_HOST),
                              ("small", SMALL_HOST))]
    if "dpm" in config:
        held.append(("dpm", DPMConfig(),
                     {k: v for k, v in config["dpm"].items() if k != "why"}))
    return [f"{group}.{k}: the file says {v!r}, the program "
            f"{getattr(obj, k)!r}"
            for group, obj, values in held for k, v in values.items()
            if getattr(obj, k) != v]


def ticks(cell: dict) -> int:
    return int(round(cell["duration_s"] / cell["tick_s"]))


def host_ticks(cells: list[dict]) -> int:
    """Simulated host-ticks of a grid: real hosts x ticks, summed over its
    clusters (padding is not work)."""
    return sum(c["n_hosts"] * ticks(c) for c in cells)
