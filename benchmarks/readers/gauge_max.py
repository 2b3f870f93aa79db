"""The largest of a gauge family's series at the measured window's end,
from the system's telemetry registry. No such gauge (a program without
it): nothing returned."""


def read(cell, run, metric: str):
    family = run["evidence"]["telemetry"]["end"].get(metric)
    if family is None:
        return None
    return max(family.values()) if isinstance(family, dict) else family
