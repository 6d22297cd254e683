"""The batched sweep's demand banks, packed straight from each cluster's
segment table: bit for bit the banks of the per-VM traces, shared across
policies exactly where the per-VM trace specs agree, and the same sweep
results as cells built from the per-VM traces."""

import dataclasses

import numpy as np
import pytest

from repro.sim import sweep as sw
from repro.sim import workloads
from repro.sim.batch import BatchCell
from repro.sim.workloads import TraceBank, TraceTable

BANK_FIELDS = ("bps", "cpu_vals", "mem_vals", "period", "rows")

#: Every trace branch of ``_sweep_traces``: the four spikes, and the
#: ``dpm`` churn's valley-then-burst.
FAMILIES = [dict(spike=s) for s in sw.SPIKES] + [dict(churn="dpm")]


def _assert_same_bank(got: TraceBank, want: TraceBank):
    assert got.vm_order == want.vm_order
    assert not got.fallback and not want.fallback
    for f in BANK_FIELDS:
        a, b = getattr(got, f), getattr(want, f)
        assert (a.dtype, a.shape) == (b.dtype, b.shape), f
        assert a.tobytes() == b.tobytes(), f


def _bank_of_traces(table: TraceTable) -> TraceBank:
    return TraceBank.from_traces(
        workloads.traces_from_table(table.vm_ids, table.segs, table.counts,
                                    table.periods), table.vm_ids)


@pytest.mark.parametrize("policy", ["cpc", "statichigh"])
@pytest.mark.parametrize("het", [False, True])
@pytest.mark.parametrize("family", FAMILIES, ids=lambda f: "-".join(
    f.values()))
def test_from_table_matches_from_traces(family, het, policy):
    spec = sw.SweepSpec(name="t", n_hosts=16, heterogeneous=het, seed=7,
                        **family)
    snap, table, _ = sw._build_cell(spec, policy)
    assert list(table.vm_ids) == list(snap.vms)
    _assert_same_bank(table.bank(), _bank_of_traces(table))


def test_from_table_ignores_segments_past_counts():
    """A prime window drawn at phase 0 opens at t = 0, so its row keeps two
    segments (``counts < k``) over a third that still holds numbers."""
    spec = sw.SweepSpec(name="t", n_hosts=8, spike="prime", seed=3)
    rng = np.random.RandomState(5)
    base = rng.uniform(600.0, 1400.0, size=spec.n_vms)
    phase = rng.uniform(0.0, 0.5, size=spec.n_vms)
    phase[[0, 5, 17]] = 0.0
    vm_ids = [f"vm{v}" for v in range(spec.n_vms)]
    table = sw._sweep_traces(spec, base, np.zeros(spec.n_hosts, bool),
                             phase, spec.n_hosts, vm_ids)
    assert sorted(np.flatnonzero(table.counts == 2)) == [0, 5, 17]
    assert np.isfinite(table.segs[[0, 5, 17], 2]).all()
    bank = table.bank()
    _assert_same_bank(bank, _bank_of_traces(table))
    assert np.isinf(bank.bps[0, 2])
    assert bank.cpu_vals[0, 2] == bank.cpu_vals[0, 1] == 0.3 * base[0]


def _same_specs(a: dict, b: dict, vm_ids) -> bool:
    """When the sweep shared one bank between two trace dicts before banks
    came from tables: every VM traced in both, with equal ``TraceSpec``s."""
    if a is b:
        return True
    for vid in vm_ids:
        sa = getattr(a.get(vid), "spec", None)
        sb = getattr(b.get(vid), "spec", None)
        if sa is None or sa != sb:
            return False
    return True


def _variants(table: TraceTable) -> list[TraceTable]:
    """``table`` itself, an equal copy, and tables that differ from it
    past the counts, in a period's non-finite value, in one valid number,
    in a count, or in a VM id."""
    def with_(**kw):
        return dataclasses.replace(table, **{
            k: np.array(getattr(table, k)) for k in ("segs", "counts",
                                                     "periods")} | kw)
    out = [table, with_()]
    short = np.flatnonzero(table.counts < table.segs.shape[1])
    if short.size:
        segs = table.segs.copy()
        segs[short[0], -1] = -1.0
        out.append(with_(segs=segs))
    periods = np.where(np.isfinite(table.periods), table.periods, np.nan)
    out.append(with_(periods=periods))
    segs = table.segs.copy()
    segs[1, 0, 1] += 1.0
    out.append(with_(segs=segs))
    counts = table.counts.copy()
    counts[2] = 1
    out.append(with_(counts=counts))
    out.append(with_(vm_ids=("x",) + table.vm_ids[1:]))
    return out


@pytest.mark.parametrize("family", FAMILIES, ids=lambda f: "-".join(
    f.values()))
def test_same_demand_shares_where_the_trace_specs_agree(family):
    """``TraceTable.same_demand`` decides as the per-VM spec comparison it
    replaced: across the deployments of one spec, and against tables that
    differ in ways a bank does or does not see."""
    spec = sw.SweepSpec(name="t", n_hosts=16, seed=11, **family)
    tables = [sw._build_cell(spec, p)[1] for p in ("cpc", "statichigh")]
    prime = sw._build_cell(
        dataclasses.replace(spec, spike="prime", churn="none"), "cpc")[1]
    prime.counts[3] = 2          # a row whose table holds more than counts
    candidates = tables + _variants(tables[0]) + _variants(prime)
    for a in candidates:
        for b in candidates:
            want = (list(a.vm_ids) == list(b.vm_ids)
                    and _same_specs(a.traces(), b.traces(), a.vm_ids))
            assert a.same_demand(b) == want


def _parent_cells(specs, policies, counters=None):
    """The grid's cells as the sweep built them from per-VM traces: each
    bank packed from ``build_sweep``'s callables, shared where every VM's
    ``TraceSpec`` agrees."""
    cells, keys = [], []
    for spec in specs:
        bank = bank_traces = None
        built = {}
        for p in policies:
            dep = "statichigh" if p == "statichigh" else "spread"
            if dep not in built:
                built[dep] = sw.build_sweep(spec, p)
            snap, traces, cfg = built[dep]
            vm_ids = list(snap.vms)
            if (bank is None or bank.vm_order != vm_ids
                    or not _same_specs(bank_traces, traces, vm_ids)):
                bank = TraceBank.from_traces(traces, vm_ids)
                bank_traces = traces
            cells.append(BatchCell(
                name=f"{spec.name}/{p}", snapshot=snap, traces=traces,
                config=cfg, powercap_enabled=(p == "cpc"),
                dpm_enabled=spec.dpm_enabled,
                balancer_enabled=spec.migration_enabled, trace_bank=bank))
            keys.append((spec, p))
    return cells, keys


#: The benchmark's two traffic families at 16 hosts and 30 minutes: two
#: clusters x three policies each.
GRIDS = {
    "spike_mix": [dict(spike="burst", heterogeneous=False),
                  dict(spike="prime", heterogeneous=True)],
    "dpm_valley_burst": [dict(spike="burst", heterogeneous=h, churn="dpm")
                         for h in (False, True)],
}
POLICIES = ("cpc", "static", "statichigh")


def _grid(family):
    return [sw.SweepSpec(name=f"{family}{i}", n_hosts=16, duration_s=1800.0,
                         seed=100 + i, **fields)
            for i, fields in enumerate(GRIDS[family])]


def _sharing(cells) -> list[int]:
    """For each cell, the index of the first cell that holds its bank."""
    first: dict = {}
    return [first.setdefault(id(c.trace_bank), i)
            for i, c in enumerate(cells)]


@pytest.mark.parametrize("family", sorted(GRIDS))
def test_grid_banks_and_lazy_traces_match_the_per_vm_build(family):
    specs = _grid(family)
    counters = {"banks_from_table": 0, "banks_from_traces": 0}
    cells, keys = sw._build_batch_cells(specs, POLICIES, counters)
    old, old_keys = _parent_cells(specs, POLICIES)
    assert keys == old_keys
    assert _sharing(cells) == _sharing(old)
    n_banks = len(set(_sharing(old)))
    assert counters == {"banks_from_table": n_banks, "banks_from_traces": 0}
    assert n_banks < len(cells)
    for c, o in zip(cells, old):
        _assert_same_bank(c.trace_bank, o.trace_bank)
    # Nothing the batched engine does with the cells makes the callables.
    from repro.sim.batch import BatchedSimulator
    assert BatchedSimulator.unsupported_cells(
        cells, sw._grid_balancer(specs)) == {}
    BatchedSimulator(cells, balancer=sw._grid_balancer(specs),
                     n_devices=1)
    assert all(c.traces._traces is None for c in cells)
    ts = np.arange(0.0, specs[0].duration_s + specs[0].tick_s,
                   specs[0].tick_s)
    for c, o in zip(cells, old):
        assert list(c.traces) == list(o.traces)
        for vid, trace in c.traces.items():
            ref = o.traces[vid]
            assert [trace(t) for t in ts] == [ref(t) for t in ts]


@pytest.mark.parametrize("family", sorted(GRIDS))
def test_grid_results_match_the_per_vm_build(family, monkeypatch):
    specs = _grid(family)
    got = sw.run_sweep(specs, POLICIES, engine="batch", n_devices=1)
    counters = sw.LAST_BATCH_INFO[0]["sweep_counters"]
    assert counters["banks_from_traces"] == 0
    assert counters["banks_from_table"] > 0
    monkeypatch.setattr(sw, "_build_batch_cells", _parent_cells)
    want = sw.run_sweep(specs, POLICIES, engine="batch", n_devices=1)
    timing = {"wall_s", "ticks_per_s"}
    for spec in specs:
        for p in POLICIES:
            g, w = (dataclasses.asdict(r[spec.name][p]) for r in (got, want))
            assert {k: v for k, v in g.items() if k not in timing} == \
                {k: v for k, v in w.items() if k not in timing}
