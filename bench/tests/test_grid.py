"""Cells as data: BENCHMARK.json, the configuration and traffic files, the
grid generator and the host-tick arithmetic."""

import importlib

import pytest

from bench import grid as G
from bench.tests.cells import load

BENCH = G.load_json(G.ROOT / "BENCHMARK.json")


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_and_makes_its_grid(w):
    entry, config, traffic = G.load_cell(w)
    assert entry["config"] == config["name"]
    assert entry["traffic"] == traffic["name"]
    cells = G.grid(config, traffic, 2**31 + 7, 1)
    assert len(cells) == config["clusters_per_grid"]
    assert {c["policy"] for c in cells} == set(traffic["policies"])
    assert all(c["churn"] == config["churn"] for c in cells)
    # Every cluster name and policy pair is distinct within a grid.
    assert len({(c["name"], c["policy"]) for c in cells}) == len(cells)


def test_configs_named_in_benchmark_json_are_their_files():
    names = {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        config = G.load_json(G.ROOT / c["file"])
        assert config["name"] == c["name"]
        assert set(c["reduced"]) <= set(config["reduced"])
    assert {w["config"] for w in BENCH["workloads"]} == names


def test_per_layer_metrics_have_readers():
    for m in BENCH["per_layer"]:
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(reader.read)
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_same_seed_same_grid_and_grids_differ():
    _, config, traffic = G.load_cell("caponly_burst")
    a = G.grid(config, traffic, 5, 1)
    assert a == G.grid(config, traffic, 5, 1)
    seeds = {c["seed"] for c in a}
    assert len(seeds) == G.seeds_per_grid(config, traffic) == 14
    other = {c["seed"] for c in G.grid(config, traffic, 5, 2)}
    assert not seeds & other
    assert all(0 <= s < G.SEED_BOUND for s in seeds)


def test_host_ticks_of_a_grid():
    _, config, traffic = load("dpm_valley")
    cells = G.grid(config, traffic, 3, 0)
    assert G.ticks(cells[0]) == 360
    # 168 clusters x 64 hosts x 360 ticks, padding not counted.
    assert G.host_ticks(cells) == 168 * 64 * 360 == 3_870_720


def test_grid_refuses_a_mismatched_mix_or_size():
    _, caponly, _ = G.load_cell("caponly_burst")
    _, _, valley = load("dpm_valley")
    with pytest.raises(ValueError, match="churn"):
        G.grid(caponly, valley, 1, 0)
    _, _, spikes = G.load_cell("caponly_burst")
    with pytest.raises(ValueError, match="multiple"):
        G.seeds_per_grid(dict(caponly, clusters_per_grid=100), spikes)


@pytest.mark.parametrize(
    "w", [w["name"] for w in BENCH["workloads"]] + ["dpm_valley"])
def test_the_program_runs_what_the_configuration_states(w):
    _, config, _ = load(w)
    assert G.program_mismatches(config) == []
    hosts = dict(config["hosts"])
    hosts["small"] = dict(hosts["small"], power_peak=250.0)
    assert G.program_mismatches(dict(config, hosts=hosts)) == [
        "hosts.small.power_peak: the file says 250.0, the program 240.0"]
