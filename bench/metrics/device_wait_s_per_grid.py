"""Host time blocked on the device scan per grid: the program's own
``batch.wait`` span (``jax.block_until_ready`` on the dispatched outputs,
before any conversion), summed over a grid's buckets and averaged over the
window's grids."""


def read(run):
    grids = run["grids"]
    buckets = [b for g in grids for b in g["buckets"]]
    if not grids or not buckets or not all("spans" in b for b in buckets):
        return None
    return sum(b["spans"]["batch.wait"] for b in buckets) / len(grids)
