"""The DPM cell's per-layer counter metric, ``evac_trips_per_drs``: its
reader on synthetic run contexts, which cells report it, and a traced CPU
run of the ``dpm_valley`` cell at a small size."""

from bench import grid as G
from bench import run as R
from bench.metrics import evac_trips_per_drs
from bench.tests.test_harness import SMALL


def _run_context(*counters):
    """Two grids; grid ``i`` holds one bucket with ``counters[i]``."""
    return {"grids": [{"buckets": [{"counters": c}]} for c in counters],
            "trace": None, "chips": 1}


def test_reads_trips_over_invocations_summed_over_buckets():
    run = _run_context(
        {"drs_invocations": 11, "evac_trips": 11 * 40, "balance_trips": 30},
        {"drs_invocations": 11, "evac_trips": 12 * 40, "balance_trips": 31})
    assert evac_trips_per_drs.read(run) == 23 * 40 / 22


def test_reads_nothing_without_the_counter():
    # The cap-only program, or a program from before the counter.
    assert evac_trips_per_drs.read(_run_context(
        {"drs_invocations": 11, "balance_trips": 30})) is None
    assert evac_trips_per_drs.read(_run_context({})) is None
    assert evac_trips_per_drs.read({"grids": [], "trace": None,
                                    "chips": 1}) is None
    assert evac_trips_per_drs.read(_run_context(
        {"drs_invocations": 0, "evac_trips": 0})) is None


def test_only_the_dpm_cell_reports_it():
    caponly = {m["name"] for m in R.per_layer_metrics("caponly_burst")}
    dpm = {m["name"] for m in R.per_layer_metrics("dpm_valley")}
    assert "evac_trips_per_drs" not in caponly
    assert dpm == caponly | {"evac_trips_per_drs"}


def test_traced_dpm_run_reports_slot_width_trips():
    """At the small size no manager invocation is deferred, so the planner
    runs its ``J`` trips at each scheduled invocation: the metric is the
    packed slot width, whole and at least ``vms_per_host``."""
    import jax
    entry, config, traffic = G.load_cell("dpm_valley")
    res = R.run(entry, dict(config, **SMALL), traffic, 2**31 + 29, 0.5,
                True, jax.devices())
    assert res["correct"] is True
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    trips = metrics["evac_trips_per_drs"]
    assert trips == int(trips) >= config["vms_per_host"]
    assert 0.0 < metrics["balance_trips_per_drs"] <= 64.0
