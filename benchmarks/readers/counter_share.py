"""A part of a counter family's growth inside the measured window, in
percent of the whole family's: the series whose label key contains
``part`` over all series of ``metric`` in the system's telemetry registry,
end minus start. Nothing counted (a program without the counter): nothing
returned."""


def read(cell, run, metric: str, part: str):
    snaps = run["evidence"]["telemetry"]

    def grown(which):
        family = snaps[which].get(metric, {})
        series = family if isinstance(family, dict) else {}
        return (sum(v for k, v in series.items() if part in k.split(",")),
                sum(series.values()))

    (part0, all0), (part1, all1) = grown("start"), grown("end")
    if all1 <= all0:
        return None
    return 100.0 * (part1 - part0) / (all1 - all0)
