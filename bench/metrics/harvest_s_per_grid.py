"""Harvest per grid once the device is done: the program's own
``batch.fetch`` (device-to-host conversions) and ``batch.check``
(post-hoc invariants, result assembly) spans, summed over a grid's buckets
and averaged over the window's grids."""


def read(run):
    grids = run["grids"]
    buckets = [b for g in grids for b in g["buckets"]]
    if not grids or not buckets or not all("spans" in b for b in buckets):
        return None
    return sum(b["spans"]["batch.fetch"] + b["spans"]["batch.check"]
               for b in buckets) / len(grids)
