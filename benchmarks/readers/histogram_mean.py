"""Mean of the samples a histogram of the system's telemetry registry took
inside the measured window: (sum at end - sum at start) over the counts'
difference, times ``scale``. Nothing observed: nothing returned."""


def read(cell, run, metric: str, label: str, scale: float = 1.0):
    snaps = run["evidence"]["telemetry"]

    def at(which):
        series = snaps[which].get(metric, {}).get(label, {})
        return series.get("count", 0), series.get("sum", 0.0)

    (n0, s0), (n1, s1) = at("start"), at("end")
    if n1 <= n0:
        return None
    return scale * (s1 - s0) / (n1 - n0)
