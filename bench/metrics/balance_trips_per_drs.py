"""BalancePowerCap loop trips per DRS invocation: the program's in-scan
``balance_trips`` counter (trips summed over a bucket's invocations; the
loop runs until the slowest cluster of the batch is done) over its
``drs_invocations``, both summed over the window's buckets."""


def read(run):
    buckets = [b for g in run["grids"] for b in g["buckets"]]
    if not buckets or not all("counters" in b for b in buckets):
        return None
    drs = sum(b["counters"]["drs_invocations"] for b in buckets)
    if drs <= 0:
        return None
    return sum(b["counters"]["balance_trips"] for b in buckets) / drs
