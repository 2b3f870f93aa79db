"""Wedge-resilient elastic execution (ISSUE 7): the deterministic fault
injector, the backend supervisor state machine, the dump_once latch,
checkpoint validation/fallback, ``fit(auto_resume=True)`` bitwise resume,
and the full serving wedge→failover→recover→swap-back cycle (in-process
and as a subprocess replica polled over HTTP)."""

import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_injector():
    from analytics_zoo_tpu.common import profiling, resilience
    resilience.install_plan(None)
    yield
    resilience.install_plan(None)
    resilience._drop_supervisor()
    # drop the flight-recorder singleton so its dump_once latch cannot
    # leak a "backend-wedged-1" trigger into the next test's episode
    profiling.reset_for_tests()


# ---------------------------------------------------------------- injector

class TestFaultInjector:
    def test_plan_grammar_windows(self):
        from analytics_zoo_tpu.common.resilience import FaultInjector
        inj = FaultInjector("wedge@dispatch:3+1,oom@step:2,wedge@probe")
        assert set(inj.sites()) == {"dispatch", "step", "probe"}
        # dispatch: arrivals 3 and 4 only
        fired = [inj.check("dispatch") is not None for _ in range(6)]
        assert fired == [False, False, True, True, False, False]
        # step: exactly arrival 2
        assert [inj.check("step") is not None for _ in range(3)] == \
            [False, True, False]
        # probe with no :start fires every call
        assert all(inj.check("probe") is not None for _ in range(4))
        assert inj.counts() == {"dispatch": 6, "step": 3, "probe": 4}

    def test_fault_carries_plan_detail(self):
        from analytics_zoo_tpu.common.resilience import FaultInjector
        f = FaultInjector("wedge@dispatch:1").check("dispatch")
        assert (f.kind, f.site, f.index) == ("wedge", "dispatch", 1)
        assert "ZOO_FAULT_PLAN" in str(f)

    def test_malformed_plan_raises(self):
        from analytics_zoo_tpu.common.resilience import FaultInjector
        with pytest.raises(ValueError, match="ZOO_FAULT_PLAN"):
            FaultInjector("wedge-dispatch-3")

    def test_malformed_env_plan_is_ignored(self, monkeypatch):
        from analytics_zoo_tpu.common import resilience
        monkeypatch.setenv("ZOO_FAULT_PLAN", "not a plan")
        resilience._INJ_LOADED = False
        resilience._INJECTOR = None
        assert resilience.get_injector() is None
        assert not resilience.fault_plan_active()

    def test_maybe_fault_raises_at_planned_arrival(self):
        from analytics_zoo_tpu.common import resilience
        resilience.install_plan("wedge@dispatch:2")
        resilience.maybe_fault("dispatch")
        with pytest.raises(resilience.InjectedFault):
            resilience.maybe_fault("dispatch")
        resilience.maybe_fault("dispatch")       # window passed

    def test_fault_scope_suppresses_nested_same_site(self):
        from analytics_zoo_tpu.common import resilience
        resilience.install_plan("wedge@dispatch:2")
        with resilience.fault_scope("dispatch"):
            # nested seam: must NOT count as arrival 2
            resilience.maybe_fault("dispatch")
            resilience.maybe_fault("dispatch")
        with pytest.raises(resilience.InjectedFault):
            with resilience.fault_scope("dispatch"):
                pass

    def test_probe_fault_is_non_raising(self):
        from analytics_zoo_tpu.common import resilience
        resilience.install_plan("wedge@probe:1")
        assert resilience.probe_fault() == "wedge"
        assert resilience.probe_fault() is None

    def test_is_backend_loss(self):
        from analytics_zoo_tpu.common import resilience

        class XlaRuntimeError(Exception):
            pass

        assert resilience.is_backend_loss(
            resilience.InjectedFault("wedge", "dispatch", 1))
        assert resilience.is_backend_loss(XlaRuntimeError("boom"))
        assert resilience.is_backend_loss(RuntimeError("device lost"))
        assert not resilience.is_backend_loss(ValueError("bad shape"))
        assert not resilience.is_backend_loss(None)

    def test_probe_seam_reaches_backend_state(self):
        from analytics_zoo_tpu.common import profiling, resilience
        resilience.install_plan("wedge@probe:1")
        st = profiling.backend_state(timeout_s=1.0)
        assert st["status"] == "wedged" and st["injected"] == "wedge"
        # plan exhausted: the next probe is a real (healthy) one
        st2 = profiling.backend_state(timeout_s=1.0)
        assert st2["status"] != "wedged"


# -------------------------------------------------------------- supervisor

def _scripted_supervisor(statuses, **kw):
    """Supervisor fed a canned probe sequence on a private registry."""
    from analytics_zoo_tpu.common import resilience, telemetry
    seq = iter(statuses)
    reg = telemetry.MetricsRegistry()
    sup = resilience.BackendSupervisor(
        probe=lambda: {"status": next(seq)}, registry=reg, **kw)
    return sup, reg


class TestBackendSupervisor:
    def test_full_cycle_and_metrics(self, tmp_path, monkeypatch):
        monkeypatch.setenv("ZOO_FLIGHT_RECORDER_DIR", str(tmp_path))
        from analytics_zoo_tpu.common.resilience import BackendSupervisor
        sup, reg = _scripted_supervisor(
            ["error", "error", "ok", "ok", "ok"], recover_probes=2)
        states = []
        for _ in range(5):
            sup.probe_once()
            states.append(sup.state)
        # the probe that flips wedged→recovering starts the healthy
        # streak, so recover_probes=2 lands ok on the next healthy probe
        assert states == ["suspect", "wedged", "recovering", "ok", "ok"]
        assert sup.episodes == 1
        snap = reg.snapshot()
        assert snap["zoo_backend_state"] == \
            BackendSupervisor.STATE_CODES["ok"]
        assert snap["zoo_backend_failovers_total"] == 1
        dumps = [p for p in os.listdir(tmp_path) if p.startswith("flightrec")]
        assert len(dumps) == 1          # one postmortem for the episode

    def test_relapse_is_same_episode_no_second_dump(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv("ZOO_FLIGHT_RECORDER_DIR", str(tmp_path))
        sup, _ = _scripted_supervisor(
            ["error", "error", "ok", "error", "ok", "ok"])
        states = [sup.probe_once() and sup.state for _ in range(6)]
        assert states[1] == "wedged"
        assert states[3] == "wedged"    # relapse from recovering
        assert states[-1] == "ok"
        assert sup.episodes == 1        # not a new episode
        dumps = [p for p in os.listdir(tmp_path) if p.startswith("flightrec")]
        assert len(dumps) == 1          # dump_once latch held

    def test_report_failure_and_force_wedged(self):
        sup, reg = _scripted_supervisor([])
        sup.report_failure(RuntimeError("device lost"))
        assert sup.state == "suspect"
        sup.report_failure(RuntimeError("device lost"))
        assert sup.state == "wedged" and sup.episodes == 1
        sup2, _ = _scripted_supervisor([])
        sup2.force_wedged("init hang")
        assert sup2.state == "wedged" and sup2.episodes == 1

    def test_probe_loop_recovers(self):
        """The daemon loop drives wedged→ok on its own once probes heal."""
        sup, _ = _scripted_supervisor([], interval_s=0.02,
                                      backoff_max_s=0.05)
        sup.force_wedged("drill")
        healthy = {"status": "ok"}
        sup._probe = lambda: healthy
        sup.ensure_started()
        try:
            deadline = time.monotonic() + 5.0
            while sup.state != "ok" and time.monotonic() < deadline:
                time.sleep(0.02)
            assert sup.state == "ok"
        finally:
            sup.stop()


class TestDumpOnce:
    def test_latch_keyed_by_trigger(self, tmp_path, monkeypatch):
        monkeypatch.setenv("ZOO_FLIGHT_RECORDER_DIR", str(tmp_path))
        from analytics_zoo_tpu.common.profiling import FlightRecorder
        fr = FlightRecorder()
        fr.note("evidence")
        p1 = fr.dump_once(trigger="backend-wedged-1", reason="backend-wedged")
        p2 = fr.dump_once(trigger="backend-wedged-1", reason="backend-wedged")
        assert p1 == p2                 # latched: same artifact back
        p3 = fr.dump_once(trigger="signal-SIGTERM", reason="sigterm")
        assert p3 != p1                 # distinct event, distinct artifact
        dumps = [p for p in os.listdir(tmp_path) if p.startswith("flightrec")]
        assert len(dumps) == 2

    def test_arm_twice_does_not_self_chain(self):
        import signal
        from analytics_zoo_tpu.common.profiling import FlightRecorder
        fr = FlightRecorder()
        if not fr.arm():
            pytest.skip("not in main thread")
        try:
            fr.arm()                    # second arm: no re-store
            prev = fr._prev_handlers.get(signal.SIGTERM)
            assert prev is not fr._handler
        finally:
            fr.disarm()


# ------------------------------------------------------------- checkpoints

class TestCheckpointValidation:
    def _state(self, scale=1.0, shape=(3, 2)):
        return {"params": {"w": np.full(shape, scale, np.float32),
                           "b": np.zeros((shape[1],), np.float32)},
                "step": np.int32(0)}

    def test_validate_state_mismatches(self):
        from analytics_zoo_tpu.learn import checkpoint as ckpt
        good = self._state()
        ckpt.validate_state(good, self._state())
        with pytest.raises(ValueError, match="shape"):
            ckpt.validate_state(self._state(shape=(4, 2)), good)
        with pytest.raises(ValueError, match="structure"):
            bad = dict(good)
            bad.pop("step")
            ckpt.validate_state(bad, good)

    def test_torn_file_falls_back_to_previous_version(self, tmp_path):
        from analytics_zoo_tpu.learn import checkpoint as ckpt
        d = str(tmp_path)
        ckpt.save_checkpoint(d, self._state(1.0), iteration=4, epoch=1)
        ckpt.save_checkpoint(d, self._state(2.0), iteration=8, epoch=2)
        # tear the newest state file in half — a crash mid-write after the
        # rename would look like this
        torn = os.path.join(d, "ckpt-8", "state.msgpack")
        blob = open(torn, "rb").read()
        with open(torn, "wb") as fh:
            fh.write(blob[:len(blob) // 2])
        got = ckpt.load_latest_checkpoint(d, self._state())
        assert got is not None
        state, meta, path = got
        assert path.endswith("ckpt-4") and meta["iteration"] == 4
        assert float(state["params"]["w"][0, 0]) == 1.0

    def test_wrong_model_checkpoint_is_skipped(self, tmp_path):
        from analytics_zoo_tpu.learn import checkpoint as ckpt
        d = str(tmp_path)
        ckpt.save_checkpoint(d, self._state(), iteration=2, epoch=1)
        ckpt.save_checkpoint(d, self._state(shape=(5, 4)), iteration=6,
                             epoch=2)
        got = ckpt.load_latest_checkpoint(d, self._state())
        assert got is not None and got[2].endswith("ckpt-2")

    def test_no_survivor_returns_none(self, tmp_path):
        from analytics_zoo_tpu.learn import checkpoint as ckpt
        assert ckpt.load_latest_checkpoint(str(tmp_path),
                                           self._state()) is None


# ------------------------------------------------------------- auto-resume

def _fit_mlp():
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = nn.Dense(16)(x)
            x = nn.relu(x)
            return nn.Dense(1)(x)
    return MLP()


def test_fit_auto_resume_bitwise_identical(orca_ctx, tmp_path):
    """Acceptance (ISSUE 7): an injected backend loss mid-epoch-3 must
    resume from the epoch-2 checkpoint at the exact step and converge to
    a BITWISE-identical final loss and params vs an unfaulted run."""
    from analytics_zoo_tpu.common import resilience
    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.learn.trigger import EveryEpoch

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = (x @ np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)) + 0.1

    def run(faulted, mdir):
        # 4 steps/epoch × 3 epochs; step arrival 10 = epoch 3, step 2 —
        # past the epoch-2 checkpoint, so resume must reload it
        resilience.install_plan("wedge@step:10" if faulted else None)
        est = Estimator.from_flax(model=_fit_mlp(), loss="mse",
                                  sample_input=x[:2], model_dir=mdir)
        hist = est.fit((x, y), epochs=3, batch_size=16,
                       checkpoint_trigger=EveryEpoch(),
                       auto_resume=faulted)
        resilience.install_plan(None)
        return est, hist

    est_a, hist_a = run(False, str(tmp_path / "a"))
    est_b, hist_b = run(True, str(tmp_path / "b"))
    assert est_a._py_step == est_b._py_step == 12
    assert hist_a["loss"][-1] == hist_b["loss"][-1]
    import jax
    for a, b in zip(jax.tree_util.tree_leaves(est_a.get_model()),
                    jax.tree_util.tree_leaves(est_b.get_model())):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_fit_auto_resume_bounded_by_env(orca_ctx, tmp_path, monkeypatch):
    """ZOO_FIT_MAX_RESUMES=0 turns auto-resume off: the injected loss
    propagates instead of retrying forever."""
    from analytics_zoo_tpu.common import resilience
    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.learn.trigger import EveryEpoch

    monkeypatch.setenv("ZOO_FIT_MAX_RESUMES", "0")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 4)).astype(np.float32)
    y = x[:, :1].copy()
    resilience.install_plan("wedge@step:3")
    est = Estimator.from_flax(model=_fit_mlp(), loss="mse",
                              sample_input=x[:2],
                              model_dir=str(tmp_path / "m"))
    with pytest.raises(resilience.InjectedFault):
        est.fit((x, y), epochs=2, batch_size=16,
                checkpoint_trigger=EveryEpoch(), auto_resume=True)


# ------------------------------------------------------- serving failover

def _tiny_inference_model():
    import flax.linen as nn
    from analytics_zoo_tpu.inference import InferenceModel

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(3)(nn.relu(nn.Dense(16)(x)))

    return InferenceModel().load_flax(Net(), np.zeros((4, 5), np.float32))


def test_serving_wedge_failover_recover_swap_back(orca_ctx, tmp_path,
                                                  monkeypatch):
    """Acceptance (ISSUE 7): full in-process cycle — wedge mid-stream,
    drain to the pre-built CPU rungs with ZERO dropped records, recover
    when probes heal, swap dispatch back to the device; the episode
    leaves exactly one ``backend-wedged`` postmortem."""
    monkeypatch.setenv("ZOO_FLIGHT_RECORDER_DIR", str(tmp_path))
    from analytics_zoo_tpu.common import resilience
    from analytics_zoo_tpu.serving import (
        Broker, ClusterServing, InputQueue, OutputQueue,
    )

    im = _tiny_inference_model()
    n = 48
    rng = np.random.default_rng(5)
    payloads = rng.standard_normal((n, 5)).astype(np.float32)
    with resilience.fault_drill("wedge@dispatch:6+2,wedge@probe:1+2"), \
            Broker.launch() as broker:
        eng = ClusterServing(im, broker.port, batch_size=4,
                             max_batch_size=4, pipeline_window=2)
        with eng.start():
            eng.wait_warm(timeout=120.0)
            in_q = InputQueue(port=broker.port)
            out_q = OutputQueue(port=broker.port)
            uris = in_q.enqueue_batch(
                (f"r{i}", {"x": payloads[i]}) for i in range(n))
            res = out_q.query_many(uris, timeout=90.0)
            assert all(v is not None for v in res.values()), \
                f"{sum(v is None for v in res.values())} records dropped"
            # drain→first-CPU-result latency was measured
            assert eng.failover_seconds and eng.failover_seconds[0] >= 0
            sup = eng._supervisor
            assert sup is not None and sup.episodes == 1
            # probes heal after the plan window: supervisor returns to ok
            # and the engine swaps dispatch back off the CPU rungs
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline and \
                    (eng.failover_active or sup.state != "ok"):
                time.sleep(0.1)
            assert sup.state == "ok"
            assert not eng.failover_active
    reasons = []
    for name in os.listdir(tmp_path):
        if name.startswith("flightrec_"):
            with open(tmp_path / name) as fh:
                reasons.append(json.load(fh)["reason"])
    assert reasons == ["backend-wedged"]


_REPLICA_SCRIPT = """
import sys
import numpy as np
import flax.linen as nn
from analytics_zoo_tpu.inference import InferenceModel
from analytics_zoo_tpu.serving.engine import ClusterServing
from analytics_zoo_tpu.serving.frontend import FrontEnd

class Net(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(3)(nn.relu(nn.Dense(16)(x)))

port = int(sys.argv[1])
im = InferenceModel().load_flax(Net(), np.zeros((4, 5), np.float32))
eng = ClusterServing(im, port, batch_size=4, max_batch_size=4,
                     pipeline_window=2)
fe = FrontEnd(port, engine=eng)
eng.start()
eng.wait_warm(timeout=120.0)
fe.start()
print("READY", fe.port, flush=True)
sys.stdin.readline()                    # parent closes stdin to stop us
eng.stop()
fe.stop()
print("DONE", flush=True)
"""


def _get_json(url, timeout=10.0):
    import urllib.error
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def test_serving_failover_subprocess_healthz_never_503(orca_ctx):
    """Acceptance (ISSUE 7): a subprocess replica armed purely through the
    environment (``ZOO_FAULT_PLAN`` + ``ZOO_CPU_FALLBACK=1``) wedges
    mid-stream, completes EVERY record via CPU failover, keeps ``/healthz``
    degraded-but-200 (never 503), and its ``records_out`` only grows."""
    from analytics_zoo_tpu.serving.broker import Broker
    from analytics_zoo_tpu.serving.client import InputQueue, OutputQueue

    env = dict(os.environ, JAX_PLATFORMS="cpu", ZOO_CPU_FALLBACK="1",
               ZOO_FAULT_PLAN="wedge@dispatch:6+2,wedge@probe:1+2")
    n = 48
    rng = np.random.default_rng(9)
    payloads = rng.standard_normal((n, 5)).astype(np.float32)
    with Broker.launch() as broker:
        proc = subprocess.Popen(
            [sys.executable, "-c", _REPLICA_SCRIPT, str(broker.port)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, cwd=REPO, env=env)
        try:
            ready = proc.stdout.readline().split()
            assert ready and ready[0] == "READY", ready
            http = int(ready[1])
            in_q = InputQueue(port=broker.port)
            out_q = OutputQueue(port=broker.port)
            uris = in_q.enqueue_batch(
                (f"w{i}", {"x": payloads[i]}) for i in range(n))
            codes, records_seen = [], []
            saw_failover = False
            deadline = time.monotonic() + 90.0
            res = {}
            while time.monotonic() < deadline:
                code, health = _get_json(
                    f"http://127.0.0.1:{http}/healthz")
                codes.append(code)
                saw_failover = saw_failover or \
                    health.get("failover") == "cpu-fallback" or \
                    health.get("status") == "degraded"
                _, m = _get_json(f"http://127.0.0.1:{http}/metrics")
                records_seen.append(int(m.get("records_out", 0)))
                res = out_q.query_many(uris, timeout=2.0)
                if all(v is not None for v in res.values()):
                    break
            missing = [u for u, v in res.items() if v is None]
            assert not missing, f"{len(missing)} records dropped"
            # /healthz stayed serving through the wedge — degraded, not down
            assert codes and all(c == 200 for c in codes), codes
            assert saw_failover, "wedge never surfaced on /healthz"
            # records_total is monotone and accounts for every record
            assert records_seen == sorted(records_seen)
            _, m = _get_json(f"http://127.0.0.1:{http}/metrics")
            assert int(m.get("records_out", 0)) == n
            # the supervisor verdict is visible from the probe endpoint
            _, health = _get_json(f"http://127.0.0.1:{http}/healthz")
            sup = health.get("backend_supervisor") or {}
            assert sup.get("episodes", 0) >= 1
        finally:
            try:
                proc.stdin.close()
            except OSError:
                pass
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)


# ------------------------------------------------- one process per chip

def test_replica_subprocess_is_pinned_to_cpu_under_a_tpu_parent(monkeypatch):
    """A chip belongs to one process. The replica's model is a numpy
    doubler: whatever JAX_PLATFORMS the parent was started with, the child
    gets ``cpu`` — ``setdefault`` used to hand a ``tpu`` parent's setting
    down, and the child would take (or hang on) the parent's device."""
    from analytics_zoo_tpu.common import resilience

    seen = {}

    class FakeProc:
        pid = 0

        class stdout:
            @staticmethod
            def readline():
                return "READY 1234 replica-x\n"

        def poll(self):
            return 0

        def wait(self, timeout=None):
            return 0

    def fake_popen(cmd, **kw):
        seen["env"] = kw["env"]
        return FakeProc()

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(subprocess, "Popen", fake_popen)
    rep = resilience.ServingReplicaProc(broker_port=1, env_extra={"K": "v"})
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert seen["env"]["K"] == "v"
    assert (rep.http_port, rep.replica_id) == (1234, "replica-x")
    # an explicit env_extra still wins: the caller owns that choice
    resilience.ServingReplicaProc(
        broker_port=1, env_extra={"JAX_PLATFORMS": "cpu,tpu"})
    assert seen["env"]["JAX_PLATFORMS"] == "cpu,tpu"
