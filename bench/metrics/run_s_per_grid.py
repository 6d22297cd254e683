"""Engine run time per grid: the program's own ``run_s`` span (dispatch to
a blocking harvest: transfer, device scan, harvest), summed over a grid's
pad buckets and averaged over the window's grids."""


def read(run):
    grids = run["grids"]
    spans = [sum(b["run_s"] for b in g["buckets"]) for g in grids]
    if not spans or not all(g["buckets"] for g in grids):
        return None
    return sum(spans) / len(spans)
