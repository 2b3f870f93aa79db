"""Resilience — deterministic fault injection + backend supervision
(ISSUE 7 tentpole).

A backend that stops answering mid-run (a lost device, a hung dispatch) is
supervised, tested behavior here rather than a hang:

- **FaultInjector** — deterministic, env-driven fault plans
  (``ZOO_FAULT_PLAN``) hooked into the dispatch/probe seams of
  ``compile_ahead.ExecutableCache``, ``pipeline_io.DevicePipeline`` and
  ``profiling.backend_state``, plus the estimator's step loop, so tests
  can wedge the backend on demand **without a TPU**. A plan is
  a comma-separated list of ``kind@site[:start[+more]]`` specs:

  - ``wedge@step:12``     — the 12th training-step dispatch raises
  - ``oom@dispatch:3``    — the 3rd device dispatch raises
  - ``wedge@dispatch:5+2``— dispatches 5..7 raise (start plus 2 more)
  - ``wedge@probe``       — every backend probe reads wedged

  Sites are counted per process by arrival order, so a plan is exactly
  reproducible. Nested seams (the pipeline's dispatch wraps the
  executable cache's) count once — the outermost seam owns the arrival.

- **BackendSupervisor** — promotes ``profiling.backend_state`` from a
  passive probe to a health state machine (``ok → suspect → wedged →
  recovering → ok``) with exponential-backoff re-probing, published as
  ``zoo_backend_state`` (numeric code) and ``zoo_backend_failovers_total``
  (transitions into ``wedged``). Every transition into ``wedged`` writes
  one flight-recorder postmortem through the ``dump_once`` latch — the
  supervisor's dump and a later SIGTERM dump cannot double-write.

- **CPU fallback gate** — ``ZOO_CPU_FALLBACK=1`` makes
  ``compile_ahead``/``InferenceModel`` pre-build a CPU executable per
  bucket rung during warmup and lets ``ClusterServing`` swap dispatch to
  them on wedge (degraded-but-serving), swapping back when the
  supervisor reports recovered.

Import cost matches telemetry.py: stdlib only at module level; jax and
profiling are imported lazily where needed (profiling imports *this*
module lazily from the probe, so the dependency stays acyclic).
"""

from __future__ import annotations

import logging
import os
import re
import signal
import subprocess
import sys
import threading
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional, Tuple

from analytics_zoo_tpu.common import telemetry

__all__ = [
    "InjectedFault", "FaultInjector", "BackendSupervisor",
    "ServingReplicaProc", "get_injector", "install_plan",
    "fault_plan_active", "maybe_fault", "fault_scope", "probe_fault",
    "fault_drill", "maybe_kill_replica", "is_backend_loss",
    "cpu_fallback_enabled", "fit_max_resumes", "get_supervisor",
    "supervisor_snapshot", "note_backend_loss", "reset_for_tests",
]

logger = logging.getLogger(__name__)

#: ``kind@site[:start[+more]]`` — kind/site are word-ish tokens
_SPEC_RE = re.compile(
    r"^(?P<kind>[a-z][a-z0-9_-]*)@(?P<site>[a-z][a-z0-9_-]*)"
    r"(?::(?P<start>\d+)(?:\+(?P<more>\d+))?)?$")

#: exception class names that read as "the backend is gone" (the jax
#: runtime raises JaxRuntimeError for device loss / DATA_LOSS); the
#: markers catch the same conditions wrapped in another exception type
_BACKEND_LOSS_TYPES = frozenset({
    "XlaRuntimeError", "JaxRuntimeError", "InternalError",
    "UnavailableError", "DeadlineExceededError",
})
_BACKEND_LOSS_MARKERS = (
    "data_loss", "device lost", "backend wedged",
    "failed to connect", "socket closed", "resource_exhausted",
    "deadline exceeded",
)


class InjectedFault(RuntimeError):
    """A fault raised by the deterministic injector. Carries the plan
    spec that fired so postmortems say *which* planned fault struck."""

    def __init__(self, kind: str, site: str, index: int):
        super().__init__(
            f"injected {kind} at {site} call #{index} (ZOO_FAULT_PLAN)")
        self.kind = kind
        self.site = site
        self.index = index


class _FaultSpec:
    __slots__ = ("kind", "site", "start", "stop")

    def __init__(self, kind: str, site: str, start: Optional[int],
                 more: int):
        self.kind = kind
        self.site = site
        self.start = start                    # None = every call
        self.stop = None if start is None else start + more

    def hits(self, index: int) -> bool:
        if self.start is None:
            return True
        return self.start <= index <= self.stop

    def __repr__(self) -> str:
        rng = "*" if self.start is None else (
            str(self.start) if self.stop == self.start
            else f"{self.start}..{self.stop}")
        return f"{self.kind}@{self.site}:{rng}"


class FaultInjector:
    """Deterministic per-site fault plan. Each site keeps an arrival
    counter; a spec fires on exact arrival indices (1-based), so the
    same plan against the same workload always wedges the same call."""

    def __init__(self, plan: str):
        self.plan = plan
        self._specs: List[_FaultSpec] = []
        self._counts: Dict[str, int] = {}
        self._lock = threading.Lock()
        for raw in plan.split(","):
            raw = raw.strip()
            if not raw:
                continue
            m = _SPEC_RE.match(raw)
            if m is None:
                raise ValueError(
                    f"bad ZOO_FAULT_PLAN spec {raw!r} — expected "
                    "kind@site[:start[+more]], e.g. wedge@dispatch:3+2")
            start = m.group("start")
            self._specs.append(_FaultSpec(
                m.group("kind"), m.group("site"),
                None if start is None else int(start),
                int(m.group("more") or 0)))

    def sites(self) -> Tuple[str, ...]:
        return tuple({s.site for s in self._specs})

    def check(self, site: str) -> Optional[InjectedFault]:
        """Count one arrival at ``site``; the planned fault for that
        index, or None. Never raises — callers decide."""
        with self._lock:
            n = self._counts.get(site, 0) + 1
            self._counts[site] = n
        for spec in self._specs:
            if spec.site == site and spec.hits(n):
                return InjectedFault(spec.kind, site, n)
        return None

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)


# process-wide injector: built lazily from ZOO_FAULT_PLAN on first use so
# subprocess tests configure it purely through the environment
_INJ_LOCK = threading.Lock()
_INJECTOR: Optional[FaultInjector] = None
_INJ_LOADED = False

# nested-seam suppression: the pipeline's dispatch seam wraps the
# executable cache's — only the outermost arrival counts
_TLS = threading.local()


def get_injector() -> Optional[FaultInjector]:
    global _INJECTOR, _INJ_LOADED
    if _INJ_LOADED:
        return _INJECTOR
    with _INJ_LOCK:
        if not _INJ_LOADED:
            plan = os.environ.get("ZOO_FAULT_PLAN", "").strip()
            if plan:
                try:
                    _INJECTOR = FaultInjector(plan)
                    logger.warning("fault plan armed: %s", plan)
                except ValueError:
                    logger.exception("ignoring malformed ZOO_FAULT_PLAN")
            _INJ_LOADED = True
    return _INJECTOR


def install_plan(plan: Optional[str]) -> Optional[FaultInjector]:
    """Install a fault plan programmatically (tests) —
    fresh counters; ``None``/empty clears."""
    global _INJECTOR, _INJ_LOADED
    with _INJ_LOCK:
        _INJECTOR = FaultInjector(plan) if plan else None
        _INJ_LOADED = True
    return _INJECTOR


def fault_plan_active() -> bool:
    return get_injector() is not None


def _suppressed(site: str) -> bool:
    return site in getattr(_TLS, "suppress", ())


def maybe_fault(site: str) -> None:
    """The injection seam: count one arrival at ``site`` and raise its
    planned fault, if any. No plan → a dict miss and out."""
    inj = get_injector()
    if inj is None or _suppressed(site):
        return
    fault = inj.check(site)
    if fault is not None:
        raise fault


@contextmanager
def fault_scope(site: str):
    """``maybe_fault(site)`` that also suppresses nested checks of the
    same site for the duration — one logical dispatch traverses both the
    pipeline seam and the executable-cache seam but arrives once."""
    inj = get_injector()
    if inj is None or _suppressed(site):
        yield
        return
    fault = inj.check(site)
    if fault is not None:
        raise fault
    sup = getattr(_TLS, "suppress", None)
    if sup is None:
        sup = _TLS.suppress = set()
    sup.add(site)
    try:
        yield
    finally:
        sup.discard(site)


def probe_fault() -> Optional[str]:
    """Non-raising probe-seam check for ``profiling.backend_state``:
    the planned fault kind for this probe arrival, or None."""
    inj = get_injector()
    if inj is None:
        return None
    fault = inj.check("probe")
    return None if fault is None else fault.kind


@contextmanager
def fault_drill(plan: str, cpu_fallback: bool = True):
    """Scoped wedge drill for tests: install ``plan`` with
    fresh counters (and force the CPU-fallback gate on), restore
    everything — injector, env, supervisor singleton — on exit."""
    prev_env = os.environ.get("ZOO_CPU_FALLBACK")
    if cpu_fallback:
        os.environ["ZOO_CPU_FALLBACK"] = "1"
    install_plan(plan)
    try:
        yield
    finally:
        install_plan(None)
        if cpu_fallback:
            if prev_env is None:
                os.environ.pop("ZOO_CPU_FALLBACK", None)
            else:
                os.environ["ZOO_CPU_FALLBACK"] = prev_env
        _drop_supervisor()


# --------------------------------------------------------- replica kill
# The crash the multi-replica delivery contract exists for: SIGKILL of a
# serving replica mid-stream (no drain, no deregister, no goodbye). The
# seam is plan-driven like every other site — ``kill@replica:N`` kills on
# the Nth arrival — so chaos drills are exactly reproducible.

_REPLICA_SCRIPT = """\
import sys, time
import numpy as np
from analytics_zoo_tpu.serving import ClusterServing, FrontEnd

class Duck:
    def __init__(self, sleep_s):
        self.sleep_s = sleep_s
    def predict(self, x):
        if self.sleep_s:
            time.sleep(self.sleep_s)   # models the accelerator round-trip
        return np.asarray(x) * 2.0

sleep_ms, port, batch = float(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
eng = ClusterServing(Duck(sleep_ms / 1000.0), port, batch_size=batch,
                     max_batch_size=batch).start()
fe = FrontEnd(port, engine=eng).start()
print("READY", fe.port, eng.replica_id, flush=True)
sys.stdin.readline()
eng.stop()
fe.stop()
"""


class ServingReplicaProc:
    """One serving replica in its own OS process (engine + frontend over
    a shared broker) — the unit :func:`maybe_kill_replica` SIGKILLs. The
    model is a duck-typed doubler whose per-batch ``predict`` sleep
    models the accelerator round-trip, so multi-replica scaling and
    failover drills measure the *delivery* layer, deterministically,
    without a device. Lease/heartbeat knobs ride ``env_extra``."""

    def __init__(self, broker_port: int, batch_size: int = 4,
                 predict_sleep_ms: float = 0.0,
                 env_extra: Optional[Dict[str, str]] = None,
                 ready_timeout_s: float = 60.0):
        env = dict(os.environ)
        # a chip belongs to one process: the replica's model is a numpy
        # doubler, so it must never inherit a parent's JAX_PLATFORMS=tpu
        # and take (or hang on) the parent's device
        env["JAX_PLATFORMS"] = "cpu"
        env.update(env_extra or {})
        self.proc = subprocess.Popen(
            [sys.executable, "-c", _REPLICA_SCRIPT,
             str(predict_sleep_ms), str(broker_port), str(batch_size)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True, env=env)
        t = threading.Thread(target=self._read_ready, daemon=True)
        t.start()
        t.join(ready_timeout_s)
        line = getattr(self, "_ready_line", "")
        parts = line.split()
        if len(parts) != 3 or parts[0] != "READY":
            self.kill()
            raise RuntimeError(
                f"serving replica failed to come up (got {line!r})")
        self.http_port = int(parts[1])
        self.replica_id = parts[2]

    def _read_ready(self):
        self._ready_line = self.proc.stdout.readline()

    @property
    def alive(self) -> bool:
        return self.proc.poll() is None

    def kill(self):
        """SIGKILL — the crash path. No drain, no deregister; the
        replica's pending entries become orphaned leases."""
        if self.proc.poll() is None:
            os.kill(self.proc.pid, signal.SIGKILL)
        self.proc.wait()

    def stop(self, timeout_s: float = 30.0):
        """Graceful path: closing stdin lets the replica run its full
        drain (stop reading → flush in-flight → ack → deregister)."""
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()
            except OSError:
                pass
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.kill()
        else:
            self.proc.wait()


def maybe_kill_replica(replica: ServingReplicaProc) -> bool:
    """The replica-kill fault seam. Counts one arrival at site
    ``replica``; when the armed plan schedules a ``kill`` for this
    arrival (``kill@replica:N``), SIGKILL the subprocess and return
    True. Call it at every natural drill checkpoint (e.g. each client
    poll round) — the plan decides which arrival strikes."""
    inj = get_injector()
    if inj is None or _suppressed("replica"):
        return False
    fault = inj.check("replica")
    if fault is None or fault.kind != "kill":
        return False
    logger.warning("injected replica kill: SIGKILL pid %d (%s)",
                   replica.proc.pid,
                   getattr(replica, "replica_id", "?"))
    replica.kill()
    return True


def is_backend_loss(err: Optional[BaseException]) -> bool:
    """Does this exception read as "the backend is gone" (vs a model/
    data bug)? Injected faults always do — that is what they model."""
    if err is None:
        return False
    if isinstance(err, InjectedFault):
        return True
    if type(err).__name__ in _BACKEND_LOSS_TYPES:
        return True
    msg = str(err).lower()
    return any(mark in msg for mark in _BACKEND_LOSS_MARKERS)


def cpu_fallback_enabled() -> bool:
    """``ZOO_CPU_FALLBACK=1``: pre-build a CPU executable per bucket rung
    during warmup and let serving fail over to them on wedge."""
    return os.environ.get("ZOO_CPU_FALLBACK", "").lower() in (
        "1", "true", "yes", "on")


def fit_max_resumes(default: int) -> int:
    """``ZOO_FIT_MAX_RESUMES`` bounds ``Estimator.fit(auto_resume=True)``
    retry-from-checkpoint attempts (default: the estimator's
    ``failure_retry_times``)."""
    raw = os.environ.get("ZOO_FIT_MAX_RESUMES", "").strip()
    try:
        return int(raw) if raw else int(default)
    except ValueError:
        return int(default)


# ------------------------------------------------------------ supervisor

class BackendSupervisor:
    """Health state machine over the backend probe.

    ``ok → suspect`` on the first failed probe (or external failure
    evidence via :meth:`report_failure`); ``suspect → wedged`` on the
    confirming failure; ``wedged → recovering`` on the first healthy
    probe; ``recovering → ok`` after ``recover_probes`` consecutive
    healthy probes (``recovering → wedged`` again on a relapse, same
    episode — no duplicate dump). While unhealthy the re-probe interval
    backs off exponentially from ``interval_s`` to ``backoff_max_s``.

    Every transition into ``wedged`` bumps ``zoo_backend_failovers_total``
    and writes one flight-recorder postmortem through the ``dump_once``
    latch (trigger ``backend-wedged-<episode>``); the current state rides
    the ``zoo_backend_state`` gauge as a numeric code.
    """

    OK, SUSPECT, WEDGED, RECOVERING = "ok", "suspect", "wedged", "recovering"
    #: gauge encoding — dashboards alert on ``zoo_backend_state >= 2``
    STATE_CODES = {OK: 0, SUSPECT: 1, WEDGED: 2, RECOVERING: 3}

    def __init__(self, probe: Optional[Callable[[], dict]] = None,
                 interval_s: float = 0.2, backoff_max_s: float = 2.0,
                 probe_timeout_s: float = 2.0, recover_probes: int = 2,
                 registry: Optional[telemetry.MetricsRegistry] = None):
        self._probe = probe or (lambda: _default_probe(probe_timeout_s))
        self.interval_s = float(interval_s)
        self.backoff_max_s = float(backoff_max_s)
        self.recover_probes = max(1, int(recover_probes))
        self._lock = threading.Lock()
        self.state = self.OK
        self.episodes = 0            # transitions into wedged
        self.last_probe: dict = {}
        self._ok_streak = 0
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        reg = registry if registry is not None else telemetry.get_registry()
        self._g_state = reg.gauge(
            "zoo_backend_state",
            "Backend supervisor state: 0 ok, 1 suspect, 2 wedged, "
            "3 recovering")
        self._c_failovers = reg.counter(
            "zoo_backend_failovers_total",
            "Supervisor transitions into the wedged state")
        self._g_state.set(0)

    # ------------------------------------------------------------ probes
    def probe_once(self) -> dict:
        """One supervised probe: run it, feed the state machine, return
        the raw probe dict."""
        try:
            st = self._probe()
        except Exception as e:   # a probe that *raises* is failure evidence
            st = {"status": "error", "error": repr(e)[:200]}
        self._observe(st)
        return st

    def report_failure(self, err: Any = None) -> None:
        """External failure evidence (a dispatch died with backend loss):
        advances the machine one failure step and wakes the re-probe loop
        so confirmation does not wait out a full healthy interval."""
        self._observe({"status": "error",
                       "error": repr(err)[:200] if err else "reported"})
        self._wake.set()

    def force_wedged(self, reason: str = "") -> None:
        """Drive straight to wedged (drills, and callers whose evidence
        is already conclusive)."""
        self._observe({"status": "error", "error": reason or "forced"})
        self._observe({"status": "wedged", "error": reason or "forced"})

    def _observe(self, st: dict) -> None:
        healthy = st.get("status") in ("ok", "jax-not-imported")
        newly_wedged = None
        with self._lock:
            self.last_probe = dict(st)
            prev = self.state
            if healthy:
                if prev == self.WEDGED:
                    self.state, self._ok_streak = self.RECOVERING, 1
                elif prev == self.RECOVERING:
                    self._ok_streak += 1
                    if self._ok_streak >= self.recover_probes:
                        self.state = self.OK
                elif prev == self.SUSPECT:
                    self.state = self.OK
            else:
                self._ok_streak = 0
                if prev == self.OK:
                    self.state = self.SUSPECT
                elif prev == self.SUSPECT:
                    self.state = self.WEDGED
                    self.episodes += 1
                    newly_wedged = self.episodes
                elif prev == self.RECOVERING:
                    # relapse: same episode, the dump_once latch holds
                    self.state = self.WEDGED
            state = self.state
            episode = self.episodes
        self._g_state.set(self.STATE_CODES[state])
        if state != prev:
            logger.warning("backend supervisor: %s -> %s (%s)",
                           prev, state, st.get("status"))
        if newly_wedged is not None:
            self._c_failovers.inc()
            self._dump_wedge(episode, st)
        elif state == self.WEDGED and prev == self.RECOVERING:
            self._dump_wedge(episode, st)   # latched: no second artifact

    def _dump_wedge(self, episode: int, st: dict) -> None:
        """One postmortem per wedge episode, through the dump_once latch
        so a SIGTERM arriving later cannot double-write this trigger."""
        try:
            from analytics_zoo_tpu.common import profiling
            fr = profiling.get_flight_recorder()
            fr.note(f"backend wedged (episode {episode}): "
                    f"{st.get('status')} {st.get('error', '')}".strip())
            path = fr.dump_once(trigger=f"backend-wedged-{episode}",
                                reason="backend-wedged")
            if path:
                logger.warning("wedge postmortem: %s", path)
        except Exception:
            logger.debug("wedge dump failed", exc_info=True)

    # ------------------------------------------------------------ thread
    def ensure_started(self) -> "BackendSupervisor":
        """Idempotently start (or restart after ``stop``) the re-probe
        daemon."""
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return self
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="zoo-backend-supervisor")
            self._thread.start()
        return self

    def _loop(self) -> None:
        delay = self.interval_s
        while not self._stop.is_set():
            woken = self._wake.wait(delay)
            if self._stop.is_set():
                return
            self._wake.clear()
            self.probe_once()
            with self._lock:
                unhealthy = self.state != self.OK
            # exponential-backoff re-probe while unhealthy; a wake (new
            # failure evidence) resets to the fast cadence
            delay = self.interval_s if (not unhealthy or woken) else \
                min(delay * 2.0, self.backoff_max_s)

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5)
        with self._lock:
            self._thread = None

    def snapshot(self) -> dict:
        with self._lock:
            return {"state": self.state, "episodes": self.episodes,
                    "last_probe": dict(self.last_probe)}


def _default_probe(timeout_s: float) -> dict:
    from analytics_zoo_tpu.common import profiling
    return profiling.backend_state(timeout_s=timeout_s)


_SUP_LOCK = threading.Lock()
_SUPERVISOR: Optional[BackendSupervisor] = None


def get_supervisor(**kwargs) -> BackendSupervisor:
    """Process-wide supervisor (created on first call; ``kwargs`` only
    apply to that creation)."""
    global _SUPERVISOR
    with _SUP_LOCK:
        if _SUPERVISOR is None:
            _SUPERVISOR = BackendSupervisor(**kwargs)
        return _SUPERVISOR


def supervisor_snapshot() -> Optional[dict]:
    """The singleton's state for health endpoints — None when no
    supervisor was ever started (probe-only deployments)."""
    with _SUP_LOCK:
        sup = _SUPERVISOR
    return None if sup is None else sup.snapshot()


def note_backend_loss(err: BaseException) -> None:
    """Feed failure evidence to the supervisor *if one is running* —
    fit's auto-resume boundary reports here without creating one."""
    with _SUP_LOCK:
        sup = _SUPERVISOR
    if sup is not None and is_backend_loss(err):
        sup.report_failure(err)


def _drop_supervisor() -> None:
    global _SUPERVISOR
    with _SUP_LOCK:
        sup, _SUPERVISOR = _SUPERVISOR, None
    if sup is not None:
        sup.stop()


def reset_for_tests() -> None:
    """Called from telemetry.reset_for_tests(): drop the injector latch
    (re-read ZOO_FAULT_PLAN next use) and stop the supervisor."""
    global _INJECTOR, _INJ_LOADED
    with _INJ_LOCK:
        _INJECTOR = None
        _INJ_LOADED = False
    _drop_supervisor()
