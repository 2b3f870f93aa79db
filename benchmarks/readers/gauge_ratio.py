"""One part of a gauge family over another at the measured window's end,
in percent: the sum of the series whose labels include ``part`` over the
sum of those whose labels include ``whole``, from the system's telemetry
registry. No such gauge, or nothing under ``whole`` (a program without
it): nothing returned."""


def read(cell, run, metric: str, part: str, whole: str):
    family = run["evidence"]["telemetry"]["end"].get(metric)
    if not isinstance(family, dict):
        return None

    def total(label):
        return sum(v for k, v in family.items() if label in k.split(","))

    denominator = total(whole)
    return 100.0 * total(part) / denominator if denominator > 0 else None
