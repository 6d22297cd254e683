"""A whole run on the CPU at a small size, past the look for a chip: the
sound program is judged correct, and the lower-precision control and each
fault planted on the timed path are judged not correct."""

import contextlib

import numpy as np
import pytest

from bench import grid as G
from bench import run as R
from bench.tests.cells import load

SMALL = dict(n_hosts=8, duration_s=1200.0, clusters_per_grid=12)


def _run(workload, seed, control=False):
    import jax
    entry, config, traffic = load(workload)
    return R.run(entry, dict(config, **SMALL), traffic, seed, 0.5, False,
                 jax.devices(), control=control)


@contextlib.contextmanager
def _planted(fault):
    """Break the batched engine's harvest: ``fault(result)`` edits the
    per-cell arrays of every batch the window's grids produce."""
    from repro.sim import batch
    harvest = batch.BatchedSimulator._harvest

    def broken(self, *a, **kw):
        res = harvest(self, *a, **kw)
        fault(res)
        return res

    batch.BatchedSimulator._harvest = broken
    try:
        yield
    finally:
        batch.BatchedSimulator._harvest = harvest


FIELDS = ("cpu_payload_mhz_s", "energy_j", "cap_changes", "vmotions",
          "power_ons", "power_offs")


def _unchanged(res):
    """The scan step returns its state unchanged: nothing accumulates."""
    for f in FIELDS:
        setattr(res, f, np.zeros_like(getattr(res, f)))


def _half(res):
    """Half of the batch left out: its clusters carry the other half's
    answers."""
    n = len(res.energy_j)
    for f in FIELDS:
        a = np.array(getattr(res, f))
        a[n // 2:] = a[:n - n // 2]
        setattr(res, f, a)


def _altered(res):
    """An answer altered where it is produced: payload off by 1e-7."""
    res.cpu_payload_mhz_s = res.cpu_payload_mhz_s * (1.0 + 1e-7)


@pytest.mark.parametrize("workload", ["caponly_burst", "dpm_valley"])
def test_sound_run_is_correct_and_control_is_not(workload):
    res = _run(workload, 2**31 + 99, control=True)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0
    assert res["attempted"] >= SMALL["clusters_per_grid"]
    assert set(res["metrics"]) == {"host_ticks_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["control"]["correct"] is False, res["control"]
    gap = res["control"]["checks"]["energy_rel_gap"]["value"]
    assert gap > 3 * res["checks"]["energy_rel_gap"]["value"]


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered])
@pytest.mark.parametrize("workload", ["caponly_burst", "dpm_valley"])
def test_planted_fault_is_not_correct(workload, fault):
    with _planted(fault):
        res = _run(workload, 12345)
    assert res["correct"] is False, res["checks"]
    assert res["failed"] > 0


def test_traced_run_reports_per_layer_metrics():
    import jax
    entry, config, traffic = G.load_cell("caponly_burst")
    res = R.run(entry, dict(config, **SMALL), traffic, 8, 0.5, True,
                jax.devices())
    assert res["correct"] is True
    # The CPU backend writes no device plane: device metrics stay out.
    assert {"prep_s_per_grid", "run_s_per_grid"} <= set(res["metrics"])
    assert res["metrics"]["run_s_per_grid"]["value"] > 0.0
    assert np.isfinite(res["device"]["window_s"])


@pytest.mark.parametrize("workload", ["caponly_burst", "dpm_valley"])
def test_sample_takes_each_policy_from_both_halves_of_the_grids(workload):
    from bench import check
    _, config, traffic = load(workload)
    grids = [{"cells": G.grid(config, traffic, 2**31 + 5, i)} for i in (1, 2)]
    picked = check.sample(grids, 2**31 + 5)
    assert len(set(picked)) == len(picked)
    for policy, n in check.SAMPLE:
        for second in (False, True):
            assert n == sum(
                grids[gi]["cells"][ci]["policy"] == policy
                and (ci >= len(grids[gi]["cells"]) // 2) == second
                for gi, ci in picked)
