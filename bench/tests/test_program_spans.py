"""A traced CPU run reports the per-layer metrics read from the program's
own spans and counters (``repro.sim.spans``, ``BatchResult.counters``)."""

from bench import grid as G
from bench import run as R
from bench.tests.test_harness import SMALL

PROGRAM_METRICS = ("build_s_per_grid", "pack_s_per_grid",
                   "device_wait_s_per_grid", "harvest_s_per_grid",
                   "balance_trips_per_drs")


def test_traced_run_reports_program_span_metrics():
    import jax
    entry, config, traffic = G.load_cell("caponly_burst")
    res = R.run(entry, dict(config, **SMALL), traffic, 2**31 + 13, 0.5,
                True, jax.devices())
    assert res["correct"] is True
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(PROGRAM_METRICS) <= set(metrics)
    for name in PROGRAM_METRICS[:4]:
        assert metrics[name] >= 0.0, name
    assert metrics["device_wait_s_per_grid"] <= metrics["run_s_per_grid"]
    assert 0.0 < metrics["balance_trips_per_drs"] <= 64.0
