"""The cells the tests run: those of ``BENCHMARK.json``, and ``dpm_valley``,
which it leaves out while the program's batched DPM evacuation departs
from the reference at full size (``PERF.md``, Open questions).  At the
tests' small size the two agree, so the DPM paths of the reference and
the harness stay tested."""

from bench import grid as G

DPM_VALLEY = {"name": "dpm_valley", "config": "drs64_dpm",
              "traffic": "dpm_valley_burst", "chips": 1}


def load(workload: str):
    """``(entry, config, traffic)`` of a cell."""
    if workload == DPM_VALLEY["name"]:
        return G.load_entry(DPM_VALLEY)
    return G.load_cell(workload)
