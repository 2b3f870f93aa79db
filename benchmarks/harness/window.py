"""Clock, compile listener and tail arithmetic shared by the drivers."""

import math
import time

#: JAX's duration events that mean a program was lowered or compiled (a
#: persistent-cache hit still lowers). Tracing of small helpers is not one.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

now = time.perf_counter


class CompileListener:
    """Counts JAX's lowerings and compilations, and the seconds they took,
    from ``jax.monitoring`` (the listener ``chip_smoke.py`` uses, copied).
    ``mark()`` at the window's start; ``since_mark()`` at its end."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self._mark = (0, 0.0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        self._mark = (self.count, self.seconds)

    def since_mark(self) -> dict:
        return {"count": self.count - self._mark[0],
                "seconds": self.seconds - self._mark[1]}


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule over ALL the
    values given: the smallest value with at least q% of them at or below
    it. ``inf`` values (missed requests) sort last, as they must."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]
