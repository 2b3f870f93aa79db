"""Profiling & flight recorder (ISSUE 3): chrome-trace export golden
structure, StepProfiler MFU/FLOPs/HBM gauges, SIGTERM postmortem dumps,
and the backend probe."""

import json
import os
import signal

import numpy as np
import pytest

from analytics_zoo_tpu.common import profiling, telemetry


@pytest.fixture(autouse=True)
def fresh_telemetry():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


def _peak_for_this_cpu(monkeypatch, flops):
    """Give the CPU's ``device_kind`` a row in the peak table: the one
    way a test makes ``fit`` publish ``zoo_mfu`` here."""
    import jax
    monkeypatch.setitem(profiling.PEAK_FLOPS,
                        jax.devices()[0].device_kind, flops)


def _record_serving_style_trace(tracer, uri="rec-0", t0=100.0):
    """A serving record's stage decomposition, deterministic timings."""
    tracer.record(uri, "total", t0, t0 + 0.010)
    tracer.record(uri, "dequeue", t0, t0 + 0.001, parent="total")
    tracer.record(uri, "preprocess", t0 + 0.001, t0 + 0.003, parent="total")
    tracer.record(uri, "device", t0 + 0.003, t0 + 0.009, parent="total")
    tracer.record(uri, "postprocess", t0 + 0.009, t0 + 0.010, parent="total")


class TestChromeTrace:
    def test_golden_structure(self):
        """The export is a Chrome Trace Event JSON object: 'M' metadata
        events naming the process and one track per trace id, 'X'
        complete events with µs timestamps relative to the earliest span
        — the exact shape Perfetto/chrome://tracing loads."""
        tracer = telemetry.get_tracer()
        _record_serving_style_trace(tracer, "rec-0", t0=100.0)
        obj = profiling.chrome_trace()
        assert obj["displayTimeUnit"] == "ms"
        ev = obj["traceEvents"]
        # round-trips through JSON (the /trace and dump_trace payload)
        assert json.loads(json.dumps(obj)) == obj

        meta = [e for e in ev if e["ph"] == "M"]
        assert {"pid", "tid", "name", "args"} <= set(meta[0])
        assert meta[0]["name"] == "process_name"
        assert meta[0]["args"]["name"] == "analytics_zoo_tpu"
        assert meta[0]["pid"] == os.getpid()
        assert [m["args"]["name"] for m in meta[1:]] == ["rec-0"]

        xs = {e["name"]: e for e in ev if e["ph"] == "X"}
        assert set(xs) == {"total", "dequeue", "preprocess", "device",
                           "postprocess"}
        for e in xs.values():
            assert {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                    "args"} <= set(e)
            assert e["cat"] == "zoo" and e["tid"] == meta[1]["tid"]
            assert e["args"]["trace_id"] == "rec-0"
        # timestamps are µs relative to the earliest span (trace opens
        # at t=0), durations µs — exact for these synthetic inputs
        assert xs["total"]["ts"] == 0.0
        assert xs["total"]["dur"] == pytest.approx(10_000.0)
        assert xs["dequeue"]["ts"] == 0.0
        assert xs["dequeue"]["dur"] == pytest.approx(1_000.0)
        assert xs["preprocess"]["ts"] == pytest.approx(1_000.0)
        assert xs["device"]["ts"] == pytest.approx(3_000.0)
        assert xs["device"]["dur"] == pytest.approx(6_000.0)
        assert xs["postprocess"]["ts"] == pytest.approx(9_000.0)
        assert xs["dequeue"]["args"]["parent"] == "total"

    def test_trace_id_filter_and_multi_track(self):
        tracer = telemetry.get_tracer()
        _record_serving_style_trace(tracer, "rec-a", t0=10.0)
        _record_serving_style_trace(tracer, "rec-b", t0=20.0)
        both = profiling.chrome_trace()
        tids = {e["tid"] for e in both["traceEvents"] if e["ph"] == "X"}
        assert len(tids) == 2, "one track (tid) per trace id"
        only = profiling.chrome_trace("rec-b")
        names = {e["args"]["name"] for e in only["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert names == {"rec-b"}

    def test_dump_trace_roundtrip_and_telemetry_delegate(self, tmp_path):
        tracer = telemetry.get_tracer()
        _record_serving_style_trace(tracer)
        p = telemetry.dump_trace(str(tmp_path / "sub" / "trace.json"))
        with open(p) as fh:
            obj = json.load(fh)
        assert obj["displayTimeUnit"] == "ms"
        assert any(e["ph"] == "X" and e["name"] == "device"
                   for e in obj["traceEvents"])

    def test_empty_tracer_is_still_valid(self):
        obj = profiling.chrome_trace()
        assert obj["traceEvents"][0]["ph"] == "M"
        assert [e for e in obj["traceEvents"] if e["ph"] == "X"] == []


class TestStepProfiler:
    def test_mfu_is_exact_for_known_inputs(self):
        """MFU = flops x n_steps / a flush window's seconds / chip peak —
        checked against hand-computed values, no hardware involved."""
        prof = profiling.StepProfiler(name="t", sample_every=1,
                                      peak_flops=1e10)
        prof.set_flops(1e9)
        prof.observe_window(n_steps=1, seconds=0.5)
        snap = telemetry.snapshot()
        assert snap["zoo_step_flops"] == 1e9
        assert snap["zoo_mfu"] == pytest.approx(1e9 / 0.5 / 1e10)
        # fused scan: flops per compiled call cover n optimizer steps
        prof2 = profiling.StepProfiler(name="t2", sample_every=1,
                                       peak_flops=1e10)
        prof2.set_flops(4e9, per_steps=4)
        prof2.observe_window(n_steps=4, seconds=0.5)
        assert telemetry.snapshot()["zoo_mfu"] == pytest.approx(
            4 * 1e9 / 0.5 / 1e10)

    def test_executable_flops_match_hand_computed_matmul(self):
        """The FLOP count kept of an ahead-of-time executable is XLA's
        cost_analysis(), which agrees with the textbook 2mnk of a matmul
        — the MFU numerator is real, not a heuristic — and building the
        same jitted function for the same signature again reads nothing
        anew."""
        import jax
        import jax.numpy as jnp

        from analytics_zoo_tpu.common import compile_ahead

        f = jax.jit(lambda a, b: a @ b)
        avals = (jax.ShapeDtypeStruct((8, 16), jnp.float32),
                 jax.ShapeDtypeStruct((16, 4), jnp.float32))
        cache = compile_ahead.ExecutableCache(f, name="matmul")
        assert cache.flops is None
        assert cache.warm(*avals)
        assert cache.flops == pytest.approx(2 * 8 * 16 * 4)
        held = profiling._executables["matmul"]
        assert "dot(" in held.hlo_text or "dot." in held.hlo_text
        again = compile_ahead.ExecutableCache(f, name="matmul")
        assert again.warm(*avals)
        assert again.flops == cache.flops
        assert profiling._executables["matmul"] is held
        # another signature of the same name replaces what is held
        wider = (avals[0], jax.ShapeDtypeStruct((16, 8), jnp.float32))
        assert again.warm(*wider)
        assert again.flops == pytest.approx(2 * 8 * 16 * 8)
        assert profiling._executables["matmul"] is not held

    def test_step_flops_helper(self, orca_ctx):
        """cost_analysis plumbing (the MFU numerator) works on this
        backend."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(a, b):
            return a @ b

        flops = None
        try:
            compiled = f.lower(jnp.ones((64, 64)),
                               jnp.ones((64, 64))).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            flops = float(ca.get("flops", 0.0))
        except Exception:
            pytest.skip("cost_analysis unavailable on this backend")
        assert flops and flops >= 2 * 64 * 64 * 64 * 0.5

    def test_no_peak_means_no_mfu(self):
        """Unknown chip (CPU): MFU is never published from a made-up
        peak; flops and phases still are."""
        prof = profiling.StepProfiler(name="t", sample_every=1,
                                      peak_flops=None)
        assert prof.peak_flops is None   # CPU: not in the table
        prof.set_flops(1e9)
        prof.observe_window(n_steps=2, seconds=0.5)
        snap = telemetry.snapshot()
        assert snap["zoo_step_flops"] == 1e9
        assert "zoo_mfu" not in snap
        assert snap["zoo_train_phase_seconds"]["phase=device"]["sum"] \
            == pytest.approx(0.25)

    def test_the_environment_cannot_replace_a_peak(self, monkeypatch):
        """The peak is the table's row for the device's kind whatever
        the two names a run could once set say."""
        for prefix in ("BENCH", "ZOO"):
            monkeypatch.setenv(prefix + "_PEAK_FLOPS", "2.5e12")
        assert profiling.device_peak_flops() is None    # CPU: no row
        assert profiling.StepProfiler(sample_every=1).peak_flops is None
        _peak_for_this_cpu(monkeypatch, 1e12)
        assert profiling.device_peak_flops() == 1e12
        assert profiling.StepProfiler(sample_every=1).peak_flops == 1e12

    def test_table_agrees_with_the_benchmarks_peaks(self):
        """Two tables state a chip's peak: ``zoo_mfu`` divides by this
        module's, the benchmark's ``step_mfu`` by ``benchmarks/peaks.json``
        (the program may not import from ``benchmarks/``). Every kind
        the benchmark names has the same FLOP/s here."""
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(repo, "benchmarks", "peaks.json")) as fh:
            peaks = json.load(fh)
        assert peaks
        for kind, row in peaks.items():
            assert profiling.PEAK_FLOPS[kind] == row["flops_per_s"], kind

    def test_phase_histogram_and_sampling(self):
        prof = profiling.StepProfiler(name="t", sample_every=4)
        assert [prof.should_sample(s) for s in range(5)] == \
            [True, False, False, False, True]
        for step in range(8):
            prof.observe_step(step, 0.0, 0.01, 0.001, callback_s=0.002)
        prof.observe_window(n_steps=8, seconds=1.6)
        with prof.phase("epoch/flush", "flush"):
            pass
        snap = telemetry.snapshot()
        h = snap["zoo_train_phase_seconds"]
        assert h["phase=data_wait"]["count"] == 8
        assert h["phase=dispatch"]["count"] == 8
        assert h["phase=callback"]["count"] == 8
        # seconds per step over a flush window: one sample per window
        assert h["phase=device"]["count"] == 1
        assert h["phase=device"]["sum"] == pytest.approx(0.2)
        assert h["phase=flush"]["count"] == 1
        # only the sampled steps left a trace
        held = [t for t in telemetry.get_tracer().traces()
                if t.startswith("t/step-")]
        assert held == ["t/step-0", "t/step-4"]
        # the phase is a tracer span too, under the profiler's own trace
        # id when no span is open around it
        assert [s.name for s in telemetry.get_tracer().get("t")] \
            == ["epoch/flush"]

    def test_sampled_step_trace_decomposition(self):
        """Sampled steps land in the tracer as a step span with
        contiguous data_wait/dispatch/callback children — the training
        analogue of the serving trace, chrome-exportable. No child
        claims the device's time: nothing in the loop waits for it."""
        prof = profiling.StepProfiler(name="train", sample_every=1)
        prof.observe_step(7, t_start=50.0, data_wait_s=0.010,
                          dispatch_s=0.002, callback_s=0.005)
        spans = {s.name: s for s in
                 telemetry.get_tracer().get("train/step-7")}
        assert set(spans) == {"step", "data_wait", "dispatch", "callback"}
        assert spans["data_wait"].start == pytest.approx(50.0)
        assert spans["data_wait"].end == pytest.approx(50.010)
        assert spans["dispatch"].start == pytest.approx(50.010)
        assert spans["dispatch"].end == pytest.approx(50.012)
        assert spans["callback"].start == pytest.approx(50.012)
        assert spans["callback"].end == spans["step"].end
        assert spans["step"].end == pytest.approx(50.017)
        for name in ("data_wait", "dispatch", "callback"):
            assert spans[name].parent == "step"
        xs = [e for e in profiling.chrome_trace("train/step-7")
              ["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in xs} == set(spans)

    def test_hbm_gauge_from_live_arrays_on_cpu(self):
        """CPU exposes no memory_stats(); the gauge falls back to summed
        live-array bytes and labels the source accordingly."""
        import jax.numpy as jnp

        keep = jnp.zeros((128, 128), jnp.float32)  # noqa: F841
        n, src = profiling.hbm_bytes()
        assert src in ("live_arrays", "memory_stats")
        assert n is not None and n >= keep.nbytes
        prof = profiling.StepProfiler(sample_every=1)
        prof.observe_window(n_steps=1, seconds=0.1)
        hbm = telemetry.snapshot()["zoo_hbm_bytes"]
        assert hbm[f"source={src}"] >= keep.nbytes

    def test_a_profiler_outlives_a_registry_reset(self):
        """The estimator keeps one profiler for as long as its step
        lives; metrics are looked up in the registry of the moment."""
        prof = profiling.StepProfiler(name="t", peak_flops=1e10)
        prof.set_flops(1e9)
        telemetry.reset_for_tests()
        prof.observe_window(n_steps=1, seconds=0.5)
        assert telemetry.snapshot()["zoo_mfu"] == pytest.approx(0.2)


class TestFitPublishesProfileMetrics:
    def _fit_tiny(self, tmp_path, **fit_args):
        import flax.linen as nn

        from analytics_zoo_tpu.learn.estimator import Estimator
        from analytics_zoo_tpu.learn.optimizers import Adam

        class Tiny(nn.Module):
            @nn.compact
            def __call__(self, x, train=False):
                return nn.Dense(1)(x)

        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 4)).astype(np.float32)
        y = x @ np.ones((4, 1), np.float32)
        est = Estimator.from_flax(model=Tiny(), loss="mse",
                                  optimizer=Adam(1e-2), sample_input=x[:2],
                                  model_dir=str(tmp_path / "m"))
        est.fit((x, y), epochs=2, batch_size=8, **fit_args)
        return est

    def test_fit_publishes_flops_mfu_hbm(self, orca_ctx, tmp_path,
                                         monkeypatch):
        """End to end through the estimator: fit() publishes
        zoo_step_flops (from the ahead-of-time executable's
        cost_analysis), zoo_mfu (a row patched into the peak table — CPU
        has none),
        zoo_hbm_bytes, and the phase histogram, all visible in the
        Prometheus exposition."""
        _peak_for_this_cpu(monkeypatch, 1e12)
        est = self._fit_tiny(tmp_path, summary_interval=4)
        est._precompile_thread.join(timeout=60)
        assert not est._precompile_thread.is_alive()
        # one more epoch: its flushes find the executable's flops set
        x = np.zeros((16, 4), np.float32)
        est.fit((x, x[:, :1]), epochs=1, batch_size=8, summary_interval=2)
        snap = telemetry.snapshot()
        # XLA's optimized-HLO count for one fwd+bwd+adam step of this
        # tiny Dense; exact hand-computed checks are in TestStepProfiler
        assert 0 < snap["zoo_step_flops"] < 1e6
        assert snap["zoo_step_flops"] == est._step_prof.flops
        assert 0 < snap["zoo_mfu"] < 1.0
        phases = snap["zoo_train_phase_seconds"]
        # 8-step epochs flushed every 4 steps, then one 2-step epoch
        assert phases["phase=data_wait"]["count"] == 18
        assert phases["phase=dispatch"]["count"] == 18
        assert phases["phase=flush"]["count"] == 5
        assert phases["phase=device"]["count"] == 5
        assert phases["phase=first_batch"]["count"] == 3
        assert phases["phase=prepare"]["count"] == 2
        hbm = snap["zoo_hbm_bytes"]
        assert sum(hbm.values()) > 0
        text = telemetry.prometheus_text()
        assert "zoo_mfu " in text and "zoo_step_flops " in text
        assert 'zoo_hbm_bytes{source="' in text
        # sampled training steps produced chrome-exportable traces, and
        # none of them pretends to hold the device's time
        xs = [e for e in profiling.chrome_trace()["traceEvents"]
              if e["ph"] == "X"
              and e["args"]["trace_id"].startswith("train/step-")]
        assert any(e["name"] == "dispatch" for e in xs)
        assert not any(e["name"] == "device" for e in xs)
        # the fit's own intervals, nested
        fit = {s.name: s for s in telemetry.get_tracer().get("train/fit-0")}
        assert {"fit", "fit/prepare", "epoch", "epoch/first_batch",
                "epoch/flush", "fit/checkpoint"} <= set(fit)
        assert fit["fit/prepare"].parent == "fit"
        assert fit["epoch/flush"].parent == "epoch"
        assert fit["fit"].start <= fit["fit/prepare"].start
        assert fit["epoch"].end <= fit["fit"].end

    def test_fit_never_fences_between_two_flushes(self, orca_ctx, tmp_path,
                                                  monkeypatch):
        """No block_until_ready, lowering or compile exists in the loop
        only to measure: the device is waited for at a flush and nowhere
        else, and zoo_mfu and zoo_step_flops are published all the
        same."""
        import jax

        _peak_for_this_cpu(monkeypatch, 1e12)
        est = self._fit_tiny(tmp_path, summary_interval=4)
        est._precompile_thread.join(timeout=60)
        fenced = []
        real_fence = jax.block_until_ready
        monkeypatch.setattr(
            jax, "block_until_ready",
            lambda x: fenced.append(1) or real_fence(x))
        telemetry.reset_for_tests()
        x = np.zeros((64, 4), np.float32)
        est.fit((x, x[:, :1]), epochs=1, batch_size=8, summary_interval=4)
        est._precompile_thread.join(timeout=60)
        assert fenced == []
        snap = telemetry.snapshot()
        assert snap["zoo_train_phase_seconds"]["phase=flush"]["count"] == 2
        assert "phase=device" in snap["zoo_train_phase_seconds"]
        assert 0 < snap["zoo_mfu"] < 1.0
        assert snap["zoo_step_flops"] > 0
        # nor was the step lowered or compiled again, by the loop or by
        # the warm-up thread (JAX's in-process caches answer both)
        assert "zoo_compile_events_total" not in snap
        assert not hasattr(profiling, "compiled_step_flops")


class TestFlightRecorder:
    def test_ring_is_fed_by_tracer_and_bounded(self):
        fr = profiling.FlightRecorder(capacity=8).attach()
        tracer = telemetry.get_tracer()
        for i in range(20):
            tracer.record(f"t{i}", "stage", 0.0, 1.0)
        snap = fr.snapshot(reason="unit")
        assert len(snap["spans"]) == 8
        assert snap["spans"][-1]["trace_id"] == "t19"
        assert snap["kind"] == "zoo_flight_recorder"
        assert snap["reason"] == "unit" and snap["pid"] == os.getpid()
        fr.detach()
        tracer.record("after", "stage", 0.0, 1.0)
        assert len(fr.snapshot()["spans"]) == 8, "detach stops feeding"

    def test_dump_contents(self, tmp_path, monkeypatch):
        monkeypatch.setenv("ZOO_DUMMY_FOR_TEST", "42")
        fr = profiling.FlightRecorder(
            capacity=4, dump_dir=str(tmp_path)).attach()
        telemetry.get_registry().counter("zoo_fr_test_total").inc(3)
        telemetry.get_tracer().record("u", "device", 1.0, 2.5)
        fr.note("part: ncf_train")
        path = fr.dump(reason="unit-test")
        assert os.path.basename(path).startswith("flightrec_")
        with open(path) as fh:
            d = json.load(fh)
        assert d["reason"] == "unit-test"
        assert d["notes"] == ["part: ncf_train"]
        assert d["env"]["ZOO_DUMMY_FOR_TEST"] == "42"
        assert d["metrics"]["zoo_fr_test_total"] == 3
        assert d["backend"]["status"] in ("ok", "jax-not-imported")
        (span,) = d["spans"]
        assert span["name"] == "device"
        assert span["duration_ms"] == pytest.approx(1500.0)

    def test_sigterm_leaves_a_dump_and_chains_handler(
            self, tmp_path, monkeypatch):
        """A simulated external kill: the armed recorder writes its
        postmortem, then chains to the previously installed handler (so
        arming never swallows someone else's SIGTERM logic)."""
        hits = []

        def prior_handler(s, f):
            hits.append(s)

        prev = signal.signal(signal.SIGTERM, prior_handler)
        try:
            monkeypatch.setenv("ZOO_FLIGHT_RECORDER", "1")
            monkeypatch.setenv("ZOO_FLIGHT_RECORDER_DIR", str(tmp_path))
            fr = profiling.maybe_arm_from_env()
            assert fr is not None
            telemetry.get_tracer().record("wedge", "device", 0.0, 9.9)
            os.kill(os.getpid(), signal.SIGTERM)
            dumps = [p for p in os.listdir(tmp_path)
                     if p.startswith("flightrec_")]
            assert len(dumps) == 1
            with open(tmp_path / dumps[0]) as fh:
                d = json.load(fh)
            assert d["reason"] == "signal-SIGTERM"
            assert [s["trace_id"] for s in d["spans"]] == ["wedge"]
            assert hits == [signal.SIGTERM], "previous handler chained"
            fr.disarm()
            # disarm restores what was in place when arm() ran
            assert signal.getsignal(signal.SIGTERM) is prior_handler
        finally:
            signal.signal(signal.SIGTERM, prev)

    def test_arm_off_main_thread_is_refused(self):
        import threading

        out = {}
        t = threading.Thread(target=lambda: out.update(
            armed=profiling.FlightRecorder().arm()))
        t.start()
        t.join()
        assert out["armed"] is False

    def test_env_gate_off_by_default(self, monkeypatch):
        monkeypatch.delenv("ZOO_FLIGHT_RECORDER", raising=False)
        assert profiling.maybe_arm_from_env() is None

    def test_dump_never_raises(self, tmp_path):
        fr = profiling.FlightRecorder(
            dump_dir=str(tmp_path / "f" / "\0bad"))
        assert fr.dump(reason="x") == ""


class TestBackendProbe:
    def test_probe_reports_cpu_backend(self):
        st = profiling.backend_state()
        assert st["status"] == "ok"
        assert st["platform"] == "cpu"
        assert st["device_count"] == 8   # conftest's virtual slice
        # second call hits the cache (still a fresh dict)
        st2 = profiling.backend_state()
        st2["status"] = "mutated"
        assert profiling.backend_state()["status"] == "ok"


class TestServingTraceEndpoint:
    def test_trace_and_healthz_backend_over_http(self):
        """GET /trace serves the chrome trace (optionally filtered) and
        /healthz now reports the backend probe — no broker needed for
        either."""
        import socket
        import urllib.error
        import urllib.request

        from analytics_zoo_tpu.serving.frontend import FrontEnd

        _record_serving_style_trace(telemetry.get_tracer(), "uri-1")
        with socket.socket() as s:           # a port nothing listens on
            s.bind(("127.0.0.1", 0))
            dead_port = s.getsockname()[1]
        with FrontEnd(dead_port).start() as fe:
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{fe.port}/trace", timeout=10)
            obj = json.loads(resp.read())
            assert resp.status == 200
            assert obj["displayTimeUnit"] == "ms"
            names = {e["name"] for e in obj["traceEvents"]
                     if e["ph"] == "X"}
            assert {"dequeue", "preprocess", "device",
                    "postprocess"} <= names
            resp2 = urllib.request.urlopen(
                f"http://127.0.0.1:{fe.port}/trace?trace_id=nope",
                timeout=10)
            obj2 = json.loads(resp2.read())
            assert [e for e in obj2["traceEvents"]
                    if e["ph"] == "X"] == []
            # healthz: broker down -> 503, but the backend probe rides
            # along and shows a live (cpu) jax backend
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{fe.port}/healthz", timeout=10)
            body = json.loads(ei.value.read())
            assert body["backend"]["status"] == "ok"
            assert body["backend"]["platform"] == "cpu"
