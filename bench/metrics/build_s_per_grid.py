"""Cluster and trace construction per grid: the program's own
``sweep.build`` span (``_build_batch_cells``), which bucket 0's record
carries under ``sweep_spans``, averaged over the window's grids."""


def read(run):
    grids = run["grids"]
    spans = [b["sweep_spans"] for g in grids for b in g["buckets"]
             if "sweep_spans" in b]
    if not grids or len(spans) != len(grids):
        return None
    return sum(s["sweep.build"] for s in spans) / len(grids)
