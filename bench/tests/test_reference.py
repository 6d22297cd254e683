"""The plain reference: its waterfill and power model against their
definitions, and, as a second witness, the whole reference against the
program's object ``Simulator``."""

import math
import random

import pytest

from bench import grid as G
from bench.reference.cluster import HostSpec, cap_for, managed_capacity, \
    waterfill
from bench.reference.scenario import COUNTS, FLOATS, run_cell
from bench.tests.cells import load


def _bisected(capacity, floors, ceils, weights):
    """The waterfill's definition, solved by bisection on the level."""
    ceils = [max(c, f) for c, f in zip(ceils, floors)]
    target = min(capacity, sum(ceils))

    def fill(level):
        return [min(max(w * level, f), c)
                for f, c, w in zip(floors, ceils, weights)]

    lo, hi = 0.0, max(c / w for c, w in zip(ceils, weights)) + 1.0
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if sum(fill(mid)) < target else (lo, mid)
    return fill(hi)


@pytest.mark.parametrize("seed", range(40))
def test_waterfill_solves_its_definition(seed):
    rng = random.Random(seed)
    n = rng.randint(1, 12)
    floors = [rng.choice([0.0, rng.uniform(0, 500)]) for _ in range(n)]
    ceils = [f + rng.choice([0.0, rng.uniform(0, 3000)]) for f in floors]
    weights = [rng.choice([1000.0, 2000.0, rng.uniform(1, 4000)])
               for _ in range(n)]
    capacity = rng.uniform(sum(floors), 1.2 * sum(ceils) + 1.0)
    got = waterfill(capacity, floors, ceils, weights)
    want = _bisected(capacity, floors, ceils, weights)
    assert sum(got) == pytest.approx(min(capacity, sum(ceils)), rel=1e-12)
    for g, w, f, c in zip(got, want, floors, ceils):
        assert f <= g <= c
        assert g == pytest.approx(w, rel=1e-9, abs=1e-9)


def test_waterfill_grants_floors_pro_rata_when_they_exceed_capacity():
    assert waterfill(300.0, [200.0, 400.0], [500.0, 500.0],
                     [1.0, 1.0]) == pytest.approx([100.0, 200.0])


def test_cap_for_inverts_the_power_model():
    spec = HostSpec(capacity_peak=34800.0, power_idle=160.0,
                    power_peak=320.0, memory_mb=98304.0,
                    hypervisor_overhead=500.0)
    for cap in (170.0, 250.0, 319.0):
        assert cap_for(spec, managed_capacity(spec, cap)) == \
            pytest.approx(cap, rel=1e-14)
    assert managed_capacity(spec, 0.0) == 0.0
    assert math.isclose(managed_capacity(spec, 400.0), 34300.0)


@pytest.mark.parametrize("workload", ["caponly_burst", "dpm_valley"])
def test_reference_matches_the_object_simulator(workload):
    from repro.sim.sweep import SweepSpec, run_cell as program_cell

    _, config, traffic = load(workload)
    config = dict(config, n_hosts=8, duration_s=3600.0, clusters_per_grid=12)
    acted = 0
    for cell in G.grid(config, traffic, 21, 0):
        want = program_cell(
            SweepSpec(**{k: v for k, v in cell.items() if k != "policy"}),
            cell["policy"], engine="legacy")
        got = run_cell(cell, config)
        assert {k: got[k] for k in COUNTS} == \
            {k: getattr(want, k) for k in COUNTS}
        for k in FLOATS:
            assert got[k] == pytest.approx(getattr(want, k), rel=1e-12)
        acted += got["cap_changes"] + got["vmotions"] + got["power_offs"]
    assert acted > 0


def test_float32_control_moves_the_floats():
    _, config, traffic = G.load_cell("caponly_burst")
    config = dict(config, n_hosts=8, duration_s=1200.0, clusters_per_grid=12)
    cell = G.grid(config, traffic, 4, 0)[0]
    hi, lo = run_cell(cell, config), run_cell(cell, config, "float32")
    gap = abs(lo["energy_j"] - hi["energy_j"]) / hi["energy_j"]
    assert 1e-9 < gap < 1e-3
    assert {k: lo[k] for k in COUNTS} == {k: hi[k] for k in COUNTS}
