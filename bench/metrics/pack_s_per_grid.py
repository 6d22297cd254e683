"""Array packing per grid: the program's own ``batch.pack`` span
(``BatchedSimulator._pack``), summed over a grid's buckets and averaged
over the window's grids."""


def read(run):
    grids = run["grids"]
    buckets = [b for g in grids for b in g["buckets"]]
    if not grids or not buckets or not all("spans" in b for b in buckets):
        return None
    return sum(b["spans"].get("batch.pack", 0.0) for b in buckets) / len(grids)
