"""What decides ``correct``: the window's clusters against the plain
reference.

After the window has closed, a sample of the clusters that the window's
grids produced, drawn from the run's seed, is run again on the plain
reference of ``bench/reference`` (scalar code written from the paper's
algorithms, importing nothing of the program), in worker processes.
The comparison covers the per-tick delivery waterfill and energy
accounting (energy and CPU payload), and every DRS-period phase through
the actions it takes (cap changes from redivvy and BalancePowerCap, DPM's
power-ons with their funding, power-offs with their reabsorption, and the
evacuation vMotions).  Every cluster of every grid must also carry a
finite, positive answer.

The numbers compared, each with its limit (``PERF.md`` gives the readings
each limit was set from):

* ``bad_cells``: clusters of the window's grids with no answer, or a
  non-finite or non-positive energy or payload.  Limit 0.
* ``count_gap``: summed absolute difference of the four action counts over
  the sampled clusters.  Exact replay of the protocol is a guarantee of the
  configuration: limit 0.
* ``energy_rel_gap`` / ``payload_rel_gap``: the largest relative gap of
  energy / CPU payload over the sampled clusters.
"""

from __future__ import annotations

import math
import multiprocessing
import os
import sys
from concurrent.futures import ProcessPoolExecutor

import numpy as np

COUNTS = ("cap_changes", "vmotions", "power_ons", "power_offs")
FLOATS = ("energy_j", "cpu_payload_mhz_s")

#: Clusters compared per run, by policy and from each half of a grid (so
#: a fault in either half of a batch shows): ``cpc`` is the mechanism
#: under test, the two static policies cover delivery and DPM without cap
#: moves.
SAMPLE = (("cpc", 4), ("static", 2), ("statichigh", 2))
WORKERS = 8

LIMITS = {
    "bad_cells": 0,
    "count_gap": 0,
    "energy_rel_gap": 1e-8,
    "payload_rel_gap": 1e-8,
}

#: Stream of the run's seed that draws the sample, apart from the grids'.
_SAMPLE_STREAM = 0x5A4D


def sample(grids: list[dict], seed: int) -> list[tuple[int, int]]:
    """``(grid position, cluster position)`` pairs drawn from the seed:
    ``SAMPLE`` clusters per policy from the first halves of the window's
    grids, and as many from their second halves."""
    rng = np.random.default_rng([int(seed) % 2**63, _SAMPLE_STREAM])
    picked = []
    for policy, n in SAMPLE:
        for second in (False, True):
            pool = [(gi, ci) for gi, g in enumerate(grids)
                    for ci, c in enumerate(g["cells"])
                    if c["policy"] == policy
                    and (ci >= len(g["cells"]) // 2) == second]
            for k in rng.choice(len(pool), size=min(n, len(pool)),
                                replace=False):
                picked.append(pool[int(k)])
    return picked


def reference(cells: list[dict], config: dict, dtypes: list[str]) -> list:
    """The reference's answers for ``cells``, each in the precision
    ``dtypes`` gives it, in ``WORKERS`` processes.  A cluster whose
    reference fails (its budget invariant, say) gets ``None``."""
    from bench.reference.scenario import run_cell

    # Each reference runs single-threaded; the workers inherit this.
    os.environ["OMP_NUM_THREADS"] = "1"
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=min(WORKERS, len(cells)),
                             mp_context=ctx) as pool:
        futs = [pool.submit(run_cell, c, config, d)
                for c, d in zip(cells, dtypes)]
        out = []
        for f in futs:
            try:
                out.append(f.result())
            except Exception as e:  # reported, and counted as a mismatch
                print(f"reference failed: {e!r}", file=sys.stderr,
                      flush=True)
                out.append(None)
    return out


def _bad(answer) -> bool:
    return answer is None or not all(
        math.isfinite(answer[f]) and answer[f] > 0.0 for f in FLOATS)


def _rel(got: float, want: float) -> float:
    return float(abs(got - want) / abs(want))


def _compare(cells, got, want, who: str) -> tuple[dict, int]:
    """Largest gaps of ``got`` against the reference's ``want``: the summed
    count gap and the largest relative energy and payload gaps, with the
    number of clusters that miss a limit."""
    count_gap = 0
    energy = payload = 0.0
    failed = 0
    for c, g, w in zip(cells, got, want):
        if _bad(g) or w is None:
            # No answer to compare: counted as a whole count and a whole
            # (100%) gap of each float.
            count_gap += 1
            energy = payload = 1.0
            failed += 1
            continue
        gap = sum(abs(int(g[k]) - int(w[k])) for k in COUNTS)
        e = _rel(g["energy_j"], w["energy_j"])
        p = _rel(g["cpu_payload_mhz_s"], w["cpu_payload_mhz_s"])
        count_gap += gap
        energy, payload = max(energy, e), max(payload, p)
        print(f"{who} {c['name']}/{c['policy']}: counts ({who}, reference) "
              f"{[(int(g[k]), int(w[k])) for k in COUNTS]}, energy gap "
              f"{e!r}, payload gap {p!r}", file=sys.stderr, flush=True)
        failed += bool(gap or e > LIMITS["energy_rel_gap"]
                       or p > LIMITS["payload_rel_gap"])
    return ({"count_gap": count_gap, "energy_rel_gap": energy,
             "payload_rel_gap": payload}, failed)


def _verdict(values: dict) -> tuple[bool, dict]:
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}
    return all(v <= LIMITS[k] for k, v in values.items()), checks


def judge(grids: list[dict], seed: int, config: dict,
          control: bool = False) -> dict:
    """Compare the window's grids against the reference; returns
    ``correct``, ``attempted``, ``failed`` and ``checks`` (each number
    compared with its limit).

    With ``control``, the same sampled clusters are also run on the
    reference computed in float32 and judged in the program's place
    (``control_correct``, ``control_checks``): the comparison has to fail
    that control (``bench/control.py``)."""
    answers = [a for g in grids for a in g["answers"]]
    bad = sum(_bad(a) for a in answers)
    picked = sample(grids, seed)
    cells = [grids[gi]["cells"][ci] for gi, ci in picked]
    got = [grids[gi]["answers"][ci] for gi, ci in picked]
    n = len(cells)
    refs = reference(cells * (2 if control else 1), config,
                     ["float64"] * n + (["float32"] * n if control else []))
    values, failed = _compare(cells, got, refs[:n], "program")
    correct, checks = _verdict({"bad_cells": bad} | values)
    verdict = {"correct": correct, "attempted": len(answers),
               "failed": bad + failed, "checks": checks}
    if control:
        cvalues, _ = _compare(cells, refs[n:], refs[:n], "control")
        verdict["control_correct"], verdict["control_checks"] = \
            _verdict(cvalues)
    return verdict
