"""Device-dispatch pipeline — bounded in-flight window for the serving and
predict hot paths.

Before this module every consumer dispatched synchronously — ``predict``
fetched its result before the next batch was even decoded, so host I/O,
preprocessing and device compute never overlapped. XLA dispatch is asynchronous by design: a jitted call
returns immediately with futures and only ``device_get``/``block_until_ready``
waits. This module packages that into a reusable **bounded in-flight window**:

- the caller keeps *submitting* host batches; each submit dispatches
  immediately (host→device staging of batch N+1 starts while batch N
  computes on the shape-bucketed executable);
- results are *retired* (fetched to host) only when the window is full or
  the stream ends — never inline with a dispatch — so up to ``window``
  batches are in flight and the device never drains between batches;
- retirement is strictly FIFO in submission order, so downstream consumers
  see ordered results no matter how the device interleaves completions.

Consumers: ``serving/engine.py`` (produce → staged-dispatch → drain serve
loop), ``inference/inference_model.py`` (chunked/streaming predict), and
``learn/estimator.py`` (predict keeps K batches in flight, ``device_get``
moved out of the batch loop).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, NamedTuple, Optional

import numpy as np

from analytics_zoo_tpu.common import resilience, telemetry


class StageTimer:
    """Per-stage wall-time stats (ref serving/utils/Timer.scala:26), plus
    unitless gauges (queue depth, overlap ratio) under ``values``.

    Re-backed onto the process-wide telemetry registry (ISSUE 2): every
    ``record`` also lands in the ``zoo_stage_seconds`` histogram (labelled
    by stage) and every ``record_value`` sets the ``zoo_stage_value``
    gauge, so StageTimer consumers show up in ``GET /metrics`` Prometheus
    exposition and ``telemetry.snapshot()`` for free. The local lists
    stay — the exact-percentile ``summary()`` API is unchanged."""

    def __init__(self, registry: Optional[telemetry.MetricsRegistry] = None):
        self._lock = threading.Lock()
        self.stats: Dict[str, List[float]] = {}
        self.values: Dict[str, List[float]] = {}
        reg = registry if registry is not None else telemetry.get_registry()
        self._hist = reg.histogram(
            "zoo_stage_seconds", "Per-stage wall time", ("stage",))
        self._gauge = reg.gauge(
            "zoo_stage_value", "Unitless per-stage samples (queue depth, "
            "overlap ratio, batch bucket)", ("stage",))

    def record(self, stage: str, dt: float):
        with self._lock:
            self.stats.setdefault(stage, []).append(dt)
        self._hist.labels(stage).observe(dt)

    def record_value(self, name: str, v: float):
        """A unitless sample (queue depth, ratio) — reported un-scaled."""
        with self._lock:
            self.values.setdefault(name, []).append(float(v))
        self._gauge.labels(name).set(v)

    def summary(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            out = {}
            for stage, xs in self.stats.items():
                arr = np.asarray(xs)
                out[stage] = {"count": len(xs), "mean_ms": float(arr.mean() * 1e3),
                              "p99_ms": float(np.percentile(arr, 99) * 1e3),
                              "total_s": float(arr.sum())}
            for name, xs in self.values.items():
                arr = np.asarray(xs)
                out[name] = {"count": len(xs), "mean": float(arr.mean()),
                             "p99": float(np.percentile(arr, 99))}
            return out


class Completed(NamedTuple):
    """One retired batch: host ``result`` (None if the batch failed),
    the caller's ``ctx`` passed at submit, the ``error`` raised by dispatch
    or fetch (None on success), and timing for stage stats.

    ``t_submit``/``dispatch_s`` place the batch on the process
    ``perf_counter`` clock so consumers (the serving engine) can turn the
    window residency into trace spans: the device span is
    ``[t_submit, t_submit + inflight_s]`` and the dispatch sub-span is
    ``[t_submit, t_submit + dispatch_s]``."""

    result: Any
    ctx: Any
    error: Optional[BaseException]
    inflight_s: float       # submit → retired (device window residency)
    fetch_s: float          # blocking part of the retirement only
    t_submit: float = 0.0   # perf_counter at dispatch
    dispatch_s: float = 0.0  # non-blocking dispatch call duration


def _default_fetch(pending):
    # d2h transfer bytes ride the zoo_device_transfer_bytes_total counter
    return telemetry.traced_device_get(pending)


class DevicePipeline:
    """Bounded in-flight dispatch window.

    ``submit_fn(batch)`` must *dispatch* work and return without blocking on
    the result (a jitted call, ``device_put``, or anything returning device
    futures). ``fetch_fn(pending)`` blocks for the host value (default
    ``jax.device_get``). At most ``window`` submitted batches are
    outstanding; the ``window+1``-th submit first retires the oldest.

    A batch whose dispatch or fetch raises retires as a ``Completed`` with
    ``error`` set — later batches are unaffected, so a stream consumer can
    fail one batch without tearing down the pipeline. ``map`` (the ordered
    generator convenience) re-raises instead.

    Not thread-safe: one pipeline belongs to one producer thread (the serve
    loop / the predict call). Use as a context manager to guarantee
    drain-on-close — no work is left in flight on exit.
    """

    def __init__(self, submit_fn: Callable[[Any], Any], window: int = 2,
                 fetch_fn: Optional[Callable[[Any], Any]] = None,
                 timer: Optional[StageTimer] = None, prefix: str = "",
                 trace_id: Optional[str] = None):
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.window = int(window)
        self._submit_fn = submit_fn
        self._fetch_fn = fetch_fn or _default_fetch
        self._timer = timer
        self._prefix = prefix
        # trace_id: sampled retired batches record their window residency
        # as tracer spans under "{trace_id}/batch-{n}" — inflight over
        # dispatch + fetch — so the predict path shows up in GET /trace
        # chrome exports alongside the serving engine's stage spans
        self._trace_id = trace_id
        self._batch_n = 0
        # (pending_device_value, ctx, t_submit, dispatch_error)
        self._q: deque = deque()

    # ------------------------------------------------------------- window
    @property
    def in_flight(self) -> int:
        return len(self._q)

    def submit(self, batch, ctx=None) -> List[Completed]:
        """Dispatch one batch. Returns the batches retired to keep the
        window bounded — empty until the window fills, then exactly the
        overflow, oldest first."""
        done = []
        while len(self._q) >= self.window:
            done.append(self._retire())
        t0 = time.perf_counter()
        try:
            # fault_scope owns the "dispatch" arrival for this batch: the
            # executable cache's seam underneath is suppressed, so a
            # planned `wedge@dispatch:N` wedges exactly the Nth batch
            with resilience.fault_scope("dispatch"):
                pending = self._submit_fn(batch)
            err = None
        except Exception as e:
            # a dispatch-time failure rides the window like any other batch
            # so it retires IN ORDER relative to its neighbours
            pending, err = None, e
        dispatch_s = time.perf_counter() - t0
        if self._timer is not None:
            self._timer.record(self._prefix + "dispatch", dispatch_s)
            self._timer.record_value(self._prefix + "window_depth",
                                     len(self._q) + 1)
        self._q.append((pending, ctx, t0, err, dispatch_s))
        return done

    def _retire(self) -> Completed:
        pending, ctx, t0, err, dispatch_s = self._q.popleft()
        if err is not None:
            return Completed(None, ctx, err, time.perf_counter() - t0, 0.0,
                             t0, dispatch_s)
        t_fetch = time.perf_counter()
        try:
            resilience.maybe_fault("fetch")
            host = self._fetch_fn(pending)
            err = None
        except Exception as e:
            host, err = None, e
        now = time.perf_counter()
        fetch_s, inflight_s = now - t_fetch, now - t0
        # the blocked fetch is the device half of the device-vs-host split
        telemetry.observe_device_block(fetch_s, self._prefix + "fetch")
        if self._timer is not None:
            self._timer.record(self._prefix + "fetch", fetch_s)
            # overlap ratio: how much of this batch's window residency the
            # host spent NOT blocked on the fetch (1.0 = compute fully
            # hidden behind host work, 0.0 = synchronous)
            self._timer.record_value(
                self._prefix + "overlap_ratio",
                1.0 - fetch_s / max(inflight_s, 1e-9))
        if self._trace_id is not None:
            n = self._batch_n
            self._batch_n += 1
            tracer = telemetry.get_tracer()
            if tracer.should_sample():
                tid = f"{self._trace_id}/batch-{n}"
                tracer.record(tid, "inflight", t0, now)
                tracer.record(tid, "dispatch", t0, t0 + dispatch_s,
                              parent="inflight")
                tracer.record(tid, "fetch", t_fetch, now,
                              parent="inflight")
        return Completed(host, ctx, err, inflight_s, fetch_s, t0, dispatch_s)

    def drain(self, max_n: Optional[int] = None) -> List[Completed]:
        """Retire up to ``max_n`` (default: all) in-flight batches, oldest
        first. Called at stream end or when the producer idles."""
        done = []
        while self._q and (max_n is None or len(done) < max_n):
            done.append(self._retire())
        return done

    # --------------------------------------------------------- convenience
    def map(self, batches: Iterable[Any]) -> Iterable[Any]:
        """Stream ``batches`` through the window, yielding host results in
        submission order. Re-raises the first failed batch's error at its
        ordered position (remaining in-flight work is dropped with it)."""
        for b in batches:
            for c in self.submit(b):
                yield self._value(c)
        for c in self.drain():
            yield self._value(c)

    @staticmethod
    def _value(c: Completed):
        if c.error is not None:
            raise c.error
        return c.result

    def __enter__(self) -> "DevicePipeline":
        return self

    def __exit__(self, *exc):
        # drain-on-close: never leave device work dangling. Results are
        # discarded (the caller already consumed what it wanted); errors
        # are swallowed — an exception mid-stream must not be masked by a
        # secondary failure surfacing here.
        self.drain()
