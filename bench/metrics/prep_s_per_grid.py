"""Sweep front-end time per grid: the grid's wall time around
``run_sweep`` minus the program's ``run_s`` of its buckets -- cluster
build, trace packing, bucket partition, compile lookup and result
assembly, host work that no grid overlaps with the device."""


def read(run):
    grids = run["grids"]
    if not grids or not all(g["buckets"] for g in grids):
        return None
    prep = [g["wall_s"] - sum(b["run_s"] for b in g["buckets"])
            for g in grids]
    return sum(prep) / len(prep)
