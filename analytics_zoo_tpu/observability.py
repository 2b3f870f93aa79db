"""Thin observability helpers over :mod:`analytics_zoo_tpu.common.telemetry`.

One import surface for operators and notebooks::

    from analytics_zoo_tpu import observability as obs
    obs.scrape()            # Prometheus text exposition of everything
    obs.metrics()           # JSON-able snapshot (counters/gauges/hist stats)
    obs.trace("my-uri")     # a served record's stage decomposition
    obs.trace_table("uri")  # ... pretty-printed

Profiling layer (ISSUE 3)::

    obs.dump_trace("out.json")        # Chrome Trace Event JSON → Perfetto
    obs.chrome_trace()                # ... as a dict (GET /trace payload)
    obs.get_flight_recorder().dump()  # postmortem under zoo_tpu_logs/
    obs.backend_state()               # non-blocking backend/device probe

Fleet & SLO layer (ISSUE 6)::

    obs.merge_snapshot(a, b)   # mergeable-snapshot algebra (federation)
    obs.fleet_registry(port=p) # list/partition live serving replicas
    obs.get_slo_monitor()      # burn-rate SLO monitor (GET /slo payload)

The serving FrontEnd exposes the same data over HTTP (``GET /metrics``
content-negotiated JSON/Prometheus — ``?scope=fleet`` for the merged
fleet view, ``?format=snapshot`` for the mergeable wire format —
``GET /healthz`` with fleet/SLO state, ``GET /trace``, ``GET /slo``);
see docs/observability.md for the stable metric catalog.
"""

from __future__ import annotations

from typing import Dict, List

from analytics_zoo_tpu.common.compile_ahead import (  # noqa: F401  (re-exports)
    WARMUP_TRACE_ID, BucketLadder, ExecutableCache, configure_persistent_cache,
)
from analytics_zoo_tpu.common.fleet import (  # noqa: F401  (re-exports)
    Heartbeater, ReplicaInfo, ReplicaRegistry,
)
from analytics_zoo_tpu.common.slo import (  # noqa: F401  (re-exports)
    SLO, SLOMonitor, default_slos,
)
from analytics_zoo_tpu.common.slo import get_monitor as get_slo_monitor  # noqa: F401
from analytics_zoo_tpu.common.profiling import (  # noqa: F401  (re-exports)
    FlightRecorder, StepProfiler, backend_state, chrome_trace,
    device_peak_flops, dump_trace, get_flight_recorder, hbm_bytes,
    maybe_arm_from_env, scope_index, step_counts,
)
from analytics_zoo_tpu.common.telemetry import (  # noqa: F401  (re-exports)
    MetricsRegistry, Span, Tracer, get_registry, get_tracer,
    instrument_jit, observe_device_block, prometheus_text, set_trace_sampling,
    snapshot, timed_block_until_ready, traced_device_get, traced_device_put,
)

__all__ = [
    "scrape", "metrics", "trace", "trace_table", "get_registry",
    "get_tracer", "instrument_jit", "set_trace_sampling",
    "prometheus_text", "snapshot", "traced_device_put", "traced_device_get",
    "observe_device_block", "timed_block_until_ready",
    "chrome_trace", "dump_trace", "StepProfiler", "FlightRecorder",
    "get_flight_recorder", "maybe_arm_from_env", "backend_state",
    "scope_index", "step_counts", "device_peak_flops", "hbm_bytes",
    "BucketLadder", "ExecutableCache", "configure_persistent_cache",
    "WARMUP_TRACE_ID",
    "merge_snapshot", "fleet_registry", "ReplicaRegistry", "ReplicaInfo",
    "Heartbeater", "SLO", "SLOMonitor", "default_slos", "get_slo_monitor",
]


def merge_snapshot(base: Dict, other: Dict) -> Dict:
    """Merge two registry snapshots (the federation algebra): counters
    and gauges sum, histograms add bucket counts and union reservoirs.
    See :meth:`MetricsRegistry.merge_snapshot`."""
    return MetricsRegistry.merge_snapshot(base, other)


def fleet_registry(host: str = "127.0.0.1", port: int = 6399
                   ) -> ReplicaRegistry:
    """A :class:`ReplicaRegistry` over the given broker — ``.list()`` /
    ``.partition()`` enumerate serving replicas by heartbeat."""
    return ReplicaRegistry(host, port)


def scrape() -> str:
    """Prometheus text exposition of the process-wide registry."""
    return prometheus_text()


def metrics() -> Dict:
    """JSON-able snapshot of the process-wide registry."""
    return snapshot()


def trace(trace_id: str) -> List[Span]:
    """All spans recorded for ``trace_id`` (a serving record's uri)."""
    return get_tracer().get(trace_id)


def trace_table(trace_id: str) -> str:
    """The trace as an aligned text table (offsets relative to the first
    span's start, durations in ms) — the quick-look CLI view."""
    spans = sorted(trace(trace_id), key=lambda s: s.start)
    if not spans:
        return f"(no trace for {trace_id!r})"
    t0 = spans[0].start
    rows = [f"{'span':<16} {'start_ms':>10} {'dur_ms':>10}  parent"]
    for s in spans:
        rows.append(f"{s.name:<16} {(s.start - t0) * 1e3:>10.3f} "
                    f"{s.duration * 1e3:>10.3f}  {s.parent or '-'}")
    return "\n".join(rows)
