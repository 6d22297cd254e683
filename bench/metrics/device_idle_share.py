"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-operation intervals / window), averaged over the
chips used."""


def read(run):
    trace = run["trace"]
    if not trace or trace["busy_s"] <= 0.0 or trace["window_s"] <= 0.0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
