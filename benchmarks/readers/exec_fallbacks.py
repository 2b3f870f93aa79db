"""Dispatches that left the ahead-of-time executables inside the window:
``ExecutableCache.fallbacks`` plus ``zoo_jit_cache_misses_total
{fn=inference_model}``, end minus start."""


def read(cell, run, metric: str = "zoo_jit_cache_misses_total",
         label: str = "fn=inference_model"):
    books = run["evidence"]["books"]

    def at(which):
        fam = books[which]["telemetry"].get(metric, {})
        misses = fam.get(label, 0.0) if isinstance(fam, dict) else 0.0
        return books[which]["fallbacks"] + float(misses or 0.0)

    return at("end") - at("start")
