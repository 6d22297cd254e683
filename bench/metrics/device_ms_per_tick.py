"""Device busy time per simulated tick of a grid: the trace's busy time
over the window (per chip), divided by the window's grids and by each
grid's ticks."""


def read(run):
    trace, grids = run["trace"], run["grids"]
    if not trace or trace["busy_s"] <= 0.0 or not grids:
        return None
    ticks = sum(g["ticks"] for g in grids)
    return 1e3 * trace["busy_s"] / ticks
