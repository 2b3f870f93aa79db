"""Profiling & flight recorder — the diagnostic layer over telemetry
(ISSUE 3 tentpole).

PR 2's telemetry collects spans and counters but cannot answer the round-5
perf questions: *where* did a step's time go, what MFU is the chip actually
sustaining, how much HBM is resident, and what was happening when a run
wedged. This module adds the four missing pieces:

- **Chrome-trace export** — serialize the process Tracer's span store to
  Chrome Trace Event JSON (loadable in Perfetto / ``chrome://tracing``):
  :func:`chrome_trace`, :func:`dump_trace`, served by the FrontEnd's
  ``GET /trace``. One track (tid) per trace id, so a serving record's
  dequeue/preprocess/device/postprocess stages and a training step's
  data-wait/dispatch/device/callback phases each render as one row.
- **StepProfiler** — per-step training decomposition used by
  ``JaxEstimator.fit``: publishes ``zoo_step_flops`` (XLA
  ``cost_analysis()`` of the compiled step), ``zoo_mfu`` (flops / fenced
  step time / chip peak), ``zoo_hbm_bytes`` (``device.memory_stats()``
  with a live-array-bytes fallback for backends that expose none, e.g.
  CPU), a ``zoo_train_phase_seconds`` histogram, and sampled step traces.
- **FlightRecorder** — bounded ring buffer of recent spans + notes that
  dumps a postmortem JSON (spans, metrics snapshot, env, backend state)
  to ``zoo_tpu_logs/`` on SIGTERM or on demand. Arm with
  ``ZOO_FLIGHT_RECORDER=1``.
- **backend probe** — :func:`backend_state`, a non-blocking (daemon thread
  + join timeout) JAX backend/device-count probe, so ``GET /healthz`` can
  report a wedged or CPU-fallback backend without ever hanging the probe.

Everything degrades gracefully: no jax → ``jax-not-imported``; no
``memory_stats`` → live-array bytes; unknown chip → no MFU (never a
made-up constant). The peak-FLOPs table lives here (moved from bench.py).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
from collections import deque
from time import perf_counter
from typing import Any, Dict, Iterable, List, Optional, Tuple

from analytics_zoo_tpu.common import telemetry
from analytics_zoo_tpu.common.telemetry import Span

__all__ = [
    "PEAK_FLOPS", "device_peak_flops", "compiled_step_flops", "hbm_bytes",
    "chrome_trace", "chrome_trace_events", "dump_trace", "StepProfiler",
    "FlightRecorder", "get_flight_recorder", "maybe_arm_from_env",
    "backend_state", "DUMP_DIR", "reset_for_tests",
]

# ``<checkout>/zoo_tpu_logs``: where flight-recorder postmortems go
# (``ZOO_FLIGHT_RECORDER_DIR`` overrides), and the anchor for the compile
# cache and the autotune verdicts. Resolved from this file's location, not
# the cwd: the directory is part of the compile cache's key, so a path
# that moves with the caller never hits.
DUMP_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "zoo_tpu_logs")

# peak dense-matmul FLOP/s per chip (bf16), keyed by device_kind; override
# with BENCH_PEAK_FLOPS / ZOO_PEAK_FLOPS. bench.py re-exports this table.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def device_peak_flops(device=None) -> Optional[float]:
    """Peak FLOP/s for ``device`` (default: first visible device), from the
    env override (``BENCH_PEAK_FLOPS``/``ZOO_PEAK_FLOPS``) or the table.
    ``None`` for unknown chips (CPU backend): MFU is then not published —
    never derived from a made-up constant."""
    for var in ("BENCH_PEAK_FLOPS", "ZOO_PEAK_FLOPS"):
        if os.environ.get(var):
            return float(os.environ[var])
    try:
        if device is None:
            import jax
            device = jax.devices()[0]
        return PEAK_FLOPS.get(device.device_kind)
    except Exception:
        return None


def compiled_step_flops(jitted, *args, **kwargs) -> Optional[float]:
    """XLA's own FLOP count for one compiled call of ``jitted(*args)``.

    ``lower()`` only reads avals (shape/dtype), so it is safe to pass
    arrays whose sibling buffers were donated. Returns ``None`` when the
    backend exposes no cost analysis."""
    try:
        ca = jitted.lower(*args, **kwargs).compile().cost_analysis()
        return float(ca.get("flops", 0.0)) or None
    except Exception:
        return None


def hbm_bytes(device=None) -> Tuple[Optional[int], str]:
    """(resident device bytes, source). Source is ``memory_stats`` when
    the backend reports ``bytes_in_use`` (real TPU/GPU HBM accounting) or
    ``live_arrays`` — the summed ``nbytes`` of every live ``jax.Array`` —
    on backends like CPU where ``memory_stats()`` is ``None``."""
    try:
        import jax
        if device is None:
            device = jax.devices()[0]
        stats = None
        try:
            stats = device.memory_stats()
        except Exception:
            stats = None
        if stats and stats.get("bytes_in_use") is not None:
            return int(stats["bytes_in_use"]), "memory_stats"
        return (sum(int(getattr(a, "nbytes", 0))
                    for a in jax.live_arrays()), "live_arrays")
    except Exception:
        return None, "unavailable"


# -------------------------------------------------------- chrome trace

def chrome_trace_events(
        traces: Optional[Dict[str, List[Span]]] = None,
        tracer: Optional[telemetry.Tracer] = None) -> List[dict]:
    """Flatten a span store into Chrome Trace Event dicts.

    Complete ("ph":"X") events, timestamps in µs relative to the earliest
    span so the trace opens at t=0; one tid per trace id with a
    ``thread_name`` metadata event, so every trace renders as its own
    labeled row in Perfetto."""
    if traces is None:
        traces = (tracer or telemetry.get_tracer()).traces()
    pid = os.getpid()
    events: List[dict] = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": "analytics_zoo_tpu"}}]
    all_spans = [s for spans in traces.values() for s in spans]
    t0 = min((s.start for s in all_spans), default=0.0)
    for tid, (trace_id, spans) in enumerate(traces.items(), start=1):
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"name": trace_id}})
        for s in sorted(spans, key=lambda s: s.start):
            events.append({
                "name": s.name, "cat": "zoo", "ph": "X",
                "ts": round((s.start - t0) * 1e6, 3),
                "dur": round(s.duration * 1e6, 3),
                "pid": pid, "tid": tid,
                "args": {"trace_id": trace_id,
                         "parent": s.parent or ""}})
    return events


def chrome_trace(trace_id: Optional[str] = None,
                 tracer: Optional[telemetry.Tracer] = None) -> dict:
    """The tracer's span store as a Chrome Trace Event JSON object
    (optionally restricted to one ``trace_id``)."""
    tracer = tracer or telemetry.get_tracer()
    traces = tracer.traces()
    if trace_id is not None:
        traces = {k: v for k, v in traces.items() if k == trace_id}
    return {"displayTimeUnit": "ms",
            "traceEvents": chrome_trace_events(traces)}


def dump_trace(path: str, trace_id: Optional[str] = None,
               tracer: Optional[telemetry.Tracer] = None) -> str:
    """Write :func:`chrome_trace` to ``path``; returns the path."""
    obj = chrome_trace(trace_id, tracer=tracer)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as fh:
        json.dump(obj, fh)
    return path


# -------------------------------------------------------- step profiler

class StepProfiler:
    """Per-step training decomposition for ``JaxEstimator.fit``.

    The estimator times each phase on the host (iterator wait, dispatch
    call, fenced device time on sampled steps, callback time) and feeds
    them to :meth:`observe_step`; the profiler turns them into

    - a ``zoo_train_phase_seconds{phase=...}`` histogram (every step),
    - ``zoo_step_flops`` / ``zoo_mfu`` gauges — flops come from the
      compiled step's ``cost_analysis()`` via :meth:`set_flops`, MFU is
      flops ÷ fenced device-seconds ÷ chip peak; no peak → no MFU,
    - a ``zoo_hbm_bytes{source=...}`` gauge refreshed on sampled steps,
    - tracer spans under trace id ``{name}/step-{n}`` for sampled steps:
      ``step`` parent over contiguous ``data_wait`` / ``dispatch`` /
      ``device`` / ``callback`` children — the training analogue of the
      serving plane's dequeue/preprocess/device/postprocess traces,
      chrome-trace exportable the same way.

    Sampling (``sample_every``) bounds perturbation: fencing every step
    would serialize the host against the device and destroy the async
    dispatch the pipeline PRs bought."""

    def __init__(self, name: str = "train", sample_every: int = 10,
                 peak_flops: Optional[float] = None,
                 registry: Optional[telemetry.MetricsRegistry] = None,
                 tracer: Optional[telemetry.Tracer] = None):
        reg = registry if registry is not None else telemetry.get_registry()
        self._tracer = tracer if tracer is not None else \
            telemetry.get_tracer()
        self.name = name
        self.sample_every = max(1, int(sample_every))
        self.peak_flops = (peak_flops if peak_flops is not None
                           else device_peak_flops())
        self.flops: Optional[float] = None   # per optimizer step
        self._flops_attempted = False
        self._g_flops = reg.gauge(
            "zoo_step_flops", "FLOPs of one compiled optimizer step "
            "(XLA cost_analysis)")
        self._g_mfu = reg.gauge(
            "zoo_mfu", "Model FLOPs utilization: step flops / fenced "
            "device time / chip peak")
        self._g_hbm = reg.gauge(
            "zoo_hbm_bytes", "Resident device memory", ("source",))
        self._h_phase = reg.histogram(
            "zoo_train_phase_seconds", "Per-step training phase wall "
            "time", ("phase",))

    # ------------------------------------------------------------ flops
    def set_flops(self, flops: Optional[float], per_steps: int = 1):
        """Record the compiled step's FLOP count (``per_steps`` optimizer
        steps per compiled call, e.g. a fused scan loop)."""
        if flops:
            self.flops = float(flops) / max(1, int(per_steps))
            self._g_flops.set(self.flops)

    def ensure_flops(self, thunk, per_steps: int = 1):
        """Compute flops once via ``thunk()`` (a ``compiled_step_flops``
        call — one extra XLA compile, so attempted a single time; the
        first batch shape wins)."""
        if self._flops_attempted:
            return
        self._flops_attempted = True
        try:
            self.set_flops(thunk(), per_steps)
        except Exception:
            pass

    def should_sample(self, step: int) -> bool:
        """Sampled steps are fenced (device time measured) and traced."""
        return step % self.sample_every == 0

    # ------------------------------------------------------------ steps
    def observe_step(self, step: int, t_start: float, data_wait_s: float,
                     dispatch_s: float, device_s: Optional[float] = None,
                     callback_s: float = 0.0, n_steps: int = 1):
        """One completed step (or fused loop of ``n_steps`` optimizer
        steps), phase durations measured by the caller. ``device_s`` is
        the fenced dispatch→ready time, present only on sampled steps;
        ``t_start`` is the ``perf_counter`` when the data wait began."""
        self._h_phase.labels("data_wait").observe(data_wait_s)
        self._h_phase.labels("dispatch").observe(dispatch_s)
        if callback_s:
            self._h_phase.labels("callback").observe(callback_s)
        if device_s is None:
            return
        self._h_phase.labels("device").observe(device_s)
        if self.flops and device_s > 0 and self.peak_flops:
            self._g_mfu.set(
                self.flops * n_steps / device_s / self.peak_flops)
        n, src = hbm_bytes()
        if n is not None:
            self._g_hbm.labels(src).set(n)
        # contiguous sub-spans reconstructed from the measured durations
        tid = f"{self.name}/step-{step}"
        t_disp = t_start + data_wait_s
        t_dev_end = t_disp + device_s
        end = t_dev_end + callback_s
        self._tracer.record(tid, "step", t_start, end)
        self._tracer.record(tid, "data_wait", t_start, t_disp,
                            parent="step")
        self._tracer.record(tid, "dispatch", t_disp, t_disp + dispatch_s,
                            parent="step")
        self._tracer.record(tid, "device", t_disp, t_dev_end,
                            parent="step")
        if callback_s:
            self._tracer.record(tid, "callback", t_dev_end, end,
                                parent="step")


# ----------------------------------------------------- flight recorder

class FlightRecorder:
    """Bounded ring of recent spans + free-form notes, dumpable as a
    postmortem JSON artifact.

    ``attach()`` hooks the process tracer so every recorded span (serving
    stages, pipeline dispatch windows, sampled training steps) lands in
    the ring; ``arm()`` installs a SIGTERM handler (chaining any previous
    one) so an external kill leaves an artifact; ``dump()`` writes the
    last N spans, a full metrics snapshot, selected env, and the backend
    probe state to ``zoo_tpu_logs/flightrec_*.json``."""

    _ENV_PREFIXES = ("ZOO_", "JAX_", "XLA_", "BENCH_", "TPU_")

    def __init__(self, capacity: int = 256,
                 dump_dir: Optional[str] = None,
                 tracer: Optional[telemetry.Tracer] = None):
        self._tracer = tracer if tracer is not None else \
            telemetry.get_tracer()
        self._spans: "deque[Span]" = deque(maxlen=int(capacity))
        self._notes: "deque[str]" = deque(maxlen=64)
        self._lock = threading.Lock()
        self._attached = False
        self._prev_handlers: Dict[int, Any] = {}
        self._seq = 0
        # dump_once latch: trigger -> written path. The supervisor's
        # wedge dump and a later SIGTERM dump each own a trigger key, so
        # layered failure paths chain without double-writing an artifact.
        self._dumped: Dict[str, str] = {}
        # explicit dir wins; otherwise resolved at dump time so the env
        # override works even on a singleton created before it was set
        self.dump_dir = dump_dir

    # --------------------------------------------------------- feeding
    def observe(self, span: Span):
        self._spans.append(span)   # deque.append is atomic

    def note(self, msg: str):
        """Free-form breadcrumb (wedge notes, part names) for the dump."""
        self._notes.append(str(msg))

    def attach(self) -> "FlightRecorder":
        with self._lock:   # attach races detach on the teardown paths
            if not self._attached:
                self._tracer.add_hook(self.observe)
                self._attached = True
        return self

    def detach(self):
        with self._lock:
            if self._attached:
                self._tracer.remove_hook(self.observe)
                self._attached = False

    # --------------------------------------------------------- dumping
    def snapshot(self, reason: str = "") -> dict:
        spans = list(self._spans)
        env = {k: v for k, v in os.environ.items()
               if k.startswith(self._ENV_PREFIXES)}
        try:
            metrics = telemetry.snapshot()
        except Exception as e:
            metrics = {"error": repr(e)[:200]}
        return {
            "kind": "zoo_flight_recorder",
            "reason": reason,
            "pid": os.getpid(),
            "argv": list(sys.argv),
            "env": env,
            "backend": backend_state(),
            "notes": list(self._notes),
            "metrics": metrics,
            "spans": [{"trace_id": s.trace_id, "name": s.name,
                       "start": s.start, "end": s.end,
                       "duration_ms": round(s.duration * 1e3, 3),
                       "parent": s.parent} for s in spans],
        }

    def dump(self, reason: str = "", path: Optional[str] = None) -> str:
        """Write the postmortem; returns the path. Never raises — a
        failing dump on a dying process must not mask the original
        fault — returns "" on failure."""
        try:
            if path is None:
                with self._lock:
                    self._seq += 1
                    seq = self._seq
                import time
                stamp = int(time.time())   # zoolint: disable=wallclock-hotpath (dump filename)
                base = (self.dump_dir
                        or os.environ.get("ZOO_FLIGHT_RECORDER_DIR")
                        or DUMP_DIR)
                path = os.path.join(
                    base, f"flightrec_{stamp}_{os.getpid()}_{seq}.json")
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            with open(path, "w") as fh:
                json.dump(self.snapshot(reason), fh)
            return path
        except Exception:
            return ""

    def dump_once(self, trigger: str, reason: str = "",
                  path: Optional[str] = None) -> str:
        """Write at most one postmortem per ``trigger`` key for the life
        of this recorder; repeat calls return the first call's path
        (possibly "" if that dump failed — failure latches too, so a
        dying process never retries dump I/O in a loop). This is how the
        supervisor's wedge dump and the SIGTERM handler layer without
        double-dumping."""
        with self._lock:
            if trigger in self._dumped:
                return self._dumped[trigger]
        out = self.dump(reason=reason or trigger, path=path)
        with self._lock:
            self._dumped.setdefault(trigger, out)
            return self._dumped[trigger]

    # --------------------------------------------------------- signals
    def _handler(self, signum, frame):
        self.dump_once(
            trigger=f"signal-{signal.Signals(signum).name}",
            reason=f"signal-{signal.Signals(signum).name}")
        prev = self._prev_handlers.get(signum)
        if callable(prev):
            prev(signum, frame)
        elif prev == signal.SIG_DFL:
            # restore and re-deliver so the process still dies from
            # SIGTERM the way the sender expects, artifact written first
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)

    def arm(self, signals: Iterable[int] = (signal.SIGTERM,)) -> bool:
        """Install dump-on-signal handlers. Returns False (and installs
        nothing) off the main thread — CPython only allows signal
        handling there."""
        try:
            for sig in signals:
                prev = signal.signal(sig, self._handler)
                # never chain to ourselves: re-arming after a prior arm
                # would otherwise store self._handler as "previous" and
                # recurse (double-dump) on delivery
                if sig not in self._prev_handlers and \
                        prev is not self._handler:
                    self._prev_handlers[sig] = prev
        except ValueError:
            return False
        return True

    def disarm(self):
        for sig, prev in self._prev_handlers.items():
            try:
                signal.signal(sig, prev)
            except ValueError:
                pass
        self._prev_handlers.clear()


_FLIGHT_RECORDER: Optional[FlightRecorder] = None
_FR_LOCK = threading.Lock()


def get_flight_recorder(capacity: int = 256) -> FlightRecorder:
    """Process-wide flight recorder, created and tracer-attached on first
    use."""
    global _FLIGHT_RECORDER
    with _FR_LOCK:
        if _FLIGHT_RECORDER is None:
            _FLIGHT_RECORDER = FlightRecorder(capacity=capacity)
        _FLIGHT_RECORDER.attach()
        return _FLIGHT_RECORDER


def maybe_arm_from_env() -> Optional[FlightRecorder]:
    """``ZOO_FLIGHT_RECORDER=1`` → attach + arm(SIGTERM) the singleton.
    Called from long-running entrypoints (serving engine start, bench)."""
    if os.environ.get("ZOO_FLIGHT_RECORDER", "").lower() not in (
            "1", "true", "yes", "on"):
        return None
    fr = get_flight_recorder()
    fr.arm()
    return fr


# ------------------------------------------------------- backend probe

_BACKEND_CACHE: Dict[str, Any] = {}
# probe_backend is called from the serve loop, supervisors, and dump
# paths concurrently — the cache update must not interleave with clear()
_BACKEND_LOCK = threading.Lock()


def backend_state(timeout_s: float = 2.0) -> dict:
    """JAX backend/platform/device-count without ever blocking the
    caller: the probe runs in a daemon thread joined with a timeout, so a
    backend that does not answer yields ``{"status": "wedged"}`` instead
    of hanging a health endpoint. A successful probe is cached (the backend
    never changes within a process). If jax was never imported, reports
    that rather than triggering device init from a mere probe."""
    # fault-injection probe seam — checked before the success cache so a
    # planned `wedge@probe` drill works even on an already-probed process
    from analytics_zoo_tpu.common import resilience
    injected = resilience.probe_fault()
    if injected is not None:
        return {"status": "wedged", "injected": injected,
                "probe_timeout_s": timeout_s}
    if _BACKEND_CACHE.get("status") == "ok":
        return dict(_BACKEND_CACHE)
    if "jax" not in sys.modules:
        return {"status": "jax-not-imported"}
    result: Dict[str, Any] = {}

    def probe():
        try:
            import jax
            devs = jax.devices()
            result.update(status="ok", platform=devs[0].platform,
                          device_kind=devs[0].device_kind,
                          device_count=len(devs))
        except BaseException as e:
            result.update(status="error", error=repr(e)[:200])

    t = threading.Thread(target=probe, daemon=True)
    t.start()
    t.join(timeout_s)
    if not result:
        return {"status": "wedged", "probe_timeout_s": timeout_s}
    if result.get("status") == "ok":
        with _BACKEND_LOCK:
            _BACKEND_CACHE.update(result)
    return dict(result)


def reset_for_tests():
    """Called from telemetry.reset_for_tests(): drop the flight-recorder
    singleton (its tracer hook died with the trace clear) and the backend
    probe cache."""
    global _FLIGHT_RECORDER
    with _FR_LOCK:
        if _FLIGHT_RECORDER is not None:
            _FLIGHT_RECORDER.detach()
            _FLIGHT_RECORDER.disarm()
            _FLIGHT_RECORDER = None
    with _BACKEND_LOCK:
        _BACKEND_CACHE.clear()
