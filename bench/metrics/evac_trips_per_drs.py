"""DPM evacuation planner loop trips per DRS invocation: the program's
in-scan ``evac_trips`` counter (trips summed over a bucket's manager
invocations; the planner visits every slot of the packed layout at each)
over its ``drs_invocations``, both summed over the window's buckets.  Only
the dynamic (DPM) program counts ``evac_trips``."""


def read(run):
    buckets = [b for g in run["grids"] for b in g["buckets"]]
    if not buckets or not all("evac_trips" in b.get("counters", {})
                              for b in buckets):
        return None
    drs = sum(b["counters"]["drs_invocations"] for b in buckets)
    if drs <= 0:
        return None
    return sum(b["counters"]["evac_trips"] for b in buckets) / drs
