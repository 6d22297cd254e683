"""The batched sweep's own spans, in-scan counters and named scopes.

``run_sweep(engine="batch")`` records host spans per bucket and per call in
``LAST_BATCH_INFO`` (``repro.sim.spans``); the scan returns the
BalancePowerCap loop's trip count; every manager phase of the jitted
program sits under a ``repro/<phase>`` named scope that a device profile
groups by.
"""

import jax
import pytest

from repro.core import kernels
from repro.sim import batch
from repro.sim import sweep as sw

BUCKET_SPANS = {"batch.dispatch", "batch.wait", "batch.fetch", "batch.check"}
SWEEP_SPANS = {"sweep", "sweep.build", "sweep.partition", "sweep.assemble"}


def _two_bucket_specs():
    # 6 and 10 hosts pad to different pow2 host classes: two buckets.
    return [sw.SweepSpec(name=f"h{n}", n_hosts=n, spike="burst",
                         duration_s=900.0) for n in (6, 10)]


def _records():
    return [dict(b) for b in sw.LAST_BATCH_INFO]


def test_two_bucket_sweep_records_spans_and_counters():
    specs = _two_bucket_specs()
    sw.run_sweep(specs, engine="batch", n_devices=1)
    first = _records()
    sw.run_sweep(specs, engine="batch", n_devices=1)
    second = _records()

    for records in (first, second):
        assert len(records) == 2
        ids = {r["sweep"] for r in records}
        assert len(ids) == 1
        for i, r in enumerate(records):
            assert BUCKET_SPANS | {"batch.pack"} <= set(r["spans"])
            assert set(r["counters"]) == {"balance_trips", "drs_invocations"}
            # DRS first fires at 300 s, then every 300 s: twice in 900 s.
            assert r["counters"]["drs_invocations"] == 2
            assert ("sweep_spans" in r) == (i == 0)
            assert all(v >= 0.0 for v in r["spans"].values())
            s = r["spans"]
            assert (s["batch.dispatch"] + s["batch.wait"] + s["batch.fetch"]
                    <= r["run_s"] + 1e-3)
            assert r["pack_s"] == s["batch.pack"]
            assert r["compile_s"] == s.get("batch.compile", 0.0)
        assert set(records[0]["sweep_spans"]) == SWEEP_SPANS
        assert all(v >= 0.0 for v in records[0]["sweep_spans"].values())
    assert second[0]["sweep"] > first[0]["sweep"]
    # The programs are cached after the first call: the second compiles
    # nothing, so no ``batch.compile`` span appears.
    assert not any("batch.compile" in r["spans"] for r in second)
    assert [r["counters"] for r in second] == [r["counters"] for r in first]


@pytest.mark.parametrize("churn", ["none", "dpm"])
def test_balance_trips_match_the_numpy_loop(monkeypatch, churn):
    """One cluster under CloudPowerCap: the scan's ``balance_trips`` equals
    the rounds ``kernels.balance_caps`` returns on the NumPy backend over
    the same cluster's DRS invocations on the object plane, in the static
    and in the churn program."""
    spec = sw.SweepSpec(name="h8", n_hosts=8, spike="burst",
                        heterogeneous=True, churn=churn, duration_s=1800.0)
    rounds = []
    original = kernels.balance_caps

    def counted(*a, **kw):
        out = original(*a, **kw)
        rounds.append(int(out[2]))
        return out

    monkeypatch.setattr(kernels, "balance_caps", counted)
    sw.run_cell(spec, "cpc", engine="vector")
    monkeypatch.setattr(kernels, "balance_caps", original)
    assert sum(rounds) > 0

    sw.run_sweep([spec], policies=["cpc"], engine="batch", n_devices=1)
    (record,) = sw.LAST_BATCH_INFO
    assert record["counters"]["balance_trips"] == sum(rounds)
    # DRS at 300, 600, ..., 1500 s.
    assert record["counters"]["drs_invocations"] == 5


#: Each program's ``repro/<phase>`` scopes; together, every scope.
BASE = ("scan", "demand", "deliver", "manager/redivvy", "manager/balance")
CHURN = BASE + ("lifecycle", "dpm/trigger", "dpm/funding", "dpm/evacuation",
                "dpm/reabsorb")

PROGRAMS = {
    "static_tree": (dict(tree="two_row"), BASE + ("manager/tree",)),
    "churn_tree": (dict(tree="two_row", churn="dpm"),
                   CHURN + ("manager/tree",)),
    "churn_timed_rules": (
        dict(churn="timed_churn", rules="violation_burst"),
        CHURN + ("migration/correct", "migration/balance", "vmotion/launch",
                 "vmotion/commit", "vmotion/overhead")),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_lowered_program_carries_named_scopes(program):
    fields, scopes = PROGRAMS[program]
    spec = sw.SweepSpec(name=program, n_hosts=4, vms_per_host=4,
                        duration_s=600.0, **fields)
    cells, _ = sw._build_batch_cells([spec], ["cpc"])
    sim = batch.BatchedSimulator(cells, balancer=sw._grid_balancer([spec]),
                                 n_devices=1)
    assert sim._static.churn == ("churn" in fields)
    static, n_dev, a, _ = sim._prepare()
    with jax.enable_x64(True), batch._quiet_donation():
        text = batch._compiled_program(static, n_dev).lower(a).as_text(
            debug_info=True)
    missing = [s for s in scopes if f"repro/{s}/" not in text]
    assert not missing
