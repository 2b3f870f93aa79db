"""Compile-ahead execution tests (ISSUE 5): bucket-ladder math, the AOT
executable cache (hit/miss/fallback, zero jit recompiles on warm
dispatch), the persistent compile-cache latch, bitwise equality of
padded-to-rung vs unpadded outputs for ``InferenceModel.predict`` and
the serving drain path, and the warmup integration invariant — traffic
crossing a bucket-growth boundary with a flat recompile counter and no
serve-thread span overlapping a compile span."""

import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from analytics_zoo_tpu.common import compile_ahead, telemetry
from analytics_zoo_tpu.common.compile_ahead import (
    WARMUP_TRACE_ID,
    BucketLadder,
    ExecutableCache,
    batch_avals,
    configure_persistent_cache,
    pad_to_rung,
)


# ------------------------------------------------------------------ ladder
def test_ladder_power_of_two_rungs():
    assert BucketLadder(4, 32).rungs == (4, 8, 16, 32)
    assert BucketLadder(2, 2).rungs == (2,)
    assert BucketLadder(3).rungs == (3,)
    # a max that is not a doubling of min clamps the top rung
    assert BucketLadder(4, 24).rungs == (4, 8, 16, 24)


def test_ladder_selection_and_stepping():
    lad = BucketLadder(4, 32)
    assert lad.min == 4 and lad.max == 32
    assert lad.rung_for(1) == 4
    assert lad.rung_for(4) == 4
    assert lad.rung_for(5) == 8
    assert lad.rung_for(9) == 16
    assert lad.rung_for(1000) == 32          # clamps to the top
    assert lad.up(4) == 8 and lad.up(32) == 32
    assert lad.down(32) == 16 and lad.down(4) == 4
    assert 8 in lad and 6 not in lad
    assert list(lad) == [4, 8, 16, 32] and len(lad) == 4


def test_ladder_validation():
    with pytest.raises(ValueError):
        BucketLadder(0)
    with pytest.raises(ValueError):
        BucketLadder(8, 4)


# ----------------------------------------------------------------- padding
def test_pad_to_rung_repeats_last_row_and_observes_fraction():
    a = np.arange(6, dtype=np.float32).reshape(3, 2)
    b = np.arange(3, dtype=np.int32)
    (pa, pb) = pad_to_rung((a, b), 4, site="t_pad_unit")
    assert pa.shape == (4, 2) and pb.shape == (4,)
    np.testing.assert_array_equal(pa[:3], a)
    np.testing.assert_array_equal(pa[3], a[-1])      # repeated last row
    assert pb[3] == b[-1]
    # full batches observe 0 so the histogram mean is the true waste rate
    (same,) = pad_to_rung((a,), 3, site="t_pad_unit")
    assert same is a
    with pytest.raises(ValueError):
        pad_to_rung((a,), 2, site="t_pad_unit")
    h = telemetry.snapshot()["zoo_bucket_pad_fraction"]["site=t_pad_unit"]
    assert h["count"] == 2
    assert h["sum"] == pytest.approx(0.25)           # (4-3)/4 then 0


def test_batch_avals():
    spec = [((3,), np.dtype(np.float32)), ((2, 2), np.dtype(np.int32))]
    avals = batch_avals(spec, 8)
    assert [tuple(a.shape) for a in avals] == [(8, 3), (8, 2, 2)]
    assert [a.dtype for a in avals] == [np.float32, np.int32]


# ----------------------------------------------- persistent cache placement
@pytest.fixture
def cache_config(monkeypatch):
    """A clean slate for the cache-placement tests: no env var, no config
    value, latch reset — and the session's own setting restored after."""
    import jax
    old = jax.config.jax_compilation_cache_dir
    thresholds = (jax.config.jax_persistent_cache_min_compile_time_secs,
                  jax.config.jax_persistent_cache_min_entry_size_bytes)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    compile_ahead._reset_cache_config_for_tests()
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", old)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      thresholds[0])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      thresholds[1])
    compile_ahead._reset_cache_config_for_tests()


def test_cache_dir_defaults_to_the_checkout_whatever_the_cwd(
        cache_config, tmp_path, monkeypatch):
    """Unset, the cache lands at <checkout>/zoo_tpu_logs/xla_cache resolved
    from the package's location: the directory is part of the cache key,
    so a path that follows the cwd never hits."""
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, "zoo_tpu_logs", "xla_cache")
    monkeypatch.chdir(tmp_path)
    assert configure_persistent_cache() == want
    assert cache_config.jax_compilation_cache_dir == want
    assert os.path.isabs(want) and os.path.isdir(want)
    assert not os.path.exists(tmp_path / "zoo_tpu_logs")
    # every entry is kept: the ladder's rungs are small, fast compiles
    assert cache_config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert cache_config.jax_persistent_cache_min_entry_size_bytes == 0
    # latched: later calls answer without touching the config again
    cache_config.update("jax_compilation_cache_dir", None)
    assert configure_persistent_cache() == want
    assert cache_config.jax_compilation_cache_dir is None


def test_cache_dir_from_the_environment_is_left_alone(
        cache_config, tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory and no
    threshold in code (JAX reads its own variables)."""
    placed = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    before = (cache_config.jax_compilation_cache_dir,
              cache_config.jax_persistent_cache_min_compile_time_secs,
              cache_config.jax_persistent_cache_min_entry_size_bytes)
    assert configure_persistent_cache() == placed
    assert (cache_config.jax_compilation_cache_dir,
            cache_config.jax_persistent_cache_min_compile_time_secs,
            cache_config.jax_persistent_cache_min_entry_size_bytes) == before
    assert not os.path.exists(placed)        # not even created here


def test_cache_dir_set_in_code_is_left_alone(cache_config, tmp_path):
    mine = str(tmp_path / "user_cache")
    cache_config.update("jax_compilation_cache_dir", mine)
    assert configure_persistent_cache() == mine
    assert cache_config.jax_compilation_cache_dir == mine


def test_no_second_cache_knob_is_left():
    """ZOO_COMPILE_CACHE is gone (JAX's own variables cover it) and the
    function takes no path."""
    import inspect
    assert not inspect.signature(configure_persistent_cache).parameters
    with open(compile_ahead.__file__) as fh:
        assert "ZOO_COMPILE_CACHE" not in fh.read()


def test_init_orca_context_configures_the_cache_first(cache_config):
    """Before the process's first compile, not at the first fit."""
    from analytics_zoo_tpu import init_orca_context
    init_orca_context(cluster_mode="local")
    assert cache_config.jax_compilation_cache_dir == \
        compile_ahead.DEFAULT_CACHE_DIR


def test_inference_model_and_engine_construction_configure_the_cache(
        cache_config):
    from analytics_zoo_tpu.inference import InferenceModel
    from analytics_zoo_tpu.serving import ClusterServing
    InferenceModel()
    assert cache_config.jax_compilation_cache_dir == \
        compile_ahead.DEFAULT_CACHE_DIR
    cache_config.update("jax_compilation_cache_dir", None)
    compile_ahead._reset_cache_config_for_tests()

    class Duck:
        def predict(self, x):
            return x

    ClusterServing(Duck(), broker_port=1)
    assert cache_config.jax_compilation_cache_dir == \
        compile_ahead.DEFAULT_CACHE_DIR


def test_log_paths_are_anchored_at_the_checkout():
    from analytics_zoo_tpu.common import profiling
    from analytics_zoo_tpu.ops import autotune
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    logs = os.path.join(checkout, "zoo_tpu_logs")
    assert profiling.DUMP_DIR == logs
    assert autotune.DEFAULT_CACHE_PATH == os.path.join(logs, "autotune.json")
    assert compile_ahead.DEFAULT_CACHE_DIR == os.path.join(logs, "xla_cache")


# --------------------------------------------------------- executable cache
def _fresh_cache(fn, name):
    import jax
    reg = telemetry.MetricsRegistry()
    tracer = telemetry.Tracer()
    return ExecutableCache(jax.jit(fn), name=name, registry=reg,
                           tracer=tracer), reg, tracer


def _counter(reg, metric, name):
    return reg.snapshot().get(metric, {}).get(f"fn={name}", 0.0)


def test_cache_warm_then_hit(orca_ctx):
    import jax
    cache, reg, tracer = _fresh_cache(lambda x: x * 2.0 + 1.0, "t_warm")
    aval = jax.ShapeDtypeStruct((4, 3), np.float32)
    assert not cache.ready(aval)
    assert cache.warm(aval)
    assert cache.ready(aval) and len(cache) == 1
    assert cache.warm(aval)                          # idempotent
    x = np.ones((4, 3), np.float32)
    np.testing.assert_array_equal(np.asarray(cache(x)), x * 2.0 + 1.0)
    assert _counter(reg, "zoo_compile_cache_hits_total", "t_warm") == 1
    assert _counter(reg, "zoo_compile_cache_misses_total", "t_warm") == 0
    # exactly one timed compile, recorded as a span on the warmup trace
    hist = reg.snapshot()["zoo_compile_seconds"]["fn=t_warm"]
    assert hist["count"] == 1
    spans = tracer.get(WARMUP_TRACE_ID)
    assert [s.name for s in spans] == ["compile"]


def test_cache_miss_compiles_then_hits(orca_ctx):
    cache, reg, _ = _fresh_cache(lambda x: x - 3.0, "t_miss")
    x = np.full((2, 2), 5.0, np.float32)
    np.testing.assert_array_equal(np.asarray(cache(x)), x - 3.0)
    assert _counter(reg, "zoo_compile_cache_misses_total", "t_miss") == 1
    np.testing.assert_array_equal(np.asarray(cache(x)), x - 3.0)
    assert _counter(reg, "zoo_compile_cache_hits_total", "t_miss") == 1
    # a different shape is its own signature
    y = np.zeros((3, 2), np.float32)
    cache(y)
    assert _counter(reg, "zoo_compile_cache_misses_total", "t_miss") == 2
    assert len(cache) == 2


def test_cache_falls_back_to_callable_without_lower(orca_ctx):
    # a plain callable has no .lower — the AOT path fails, the call still
    # returns through the wrapped function and warm() reports failure
    reg = telemetry.MetricsRegistry()
    cache = ExecutableCache(lambda x: x * 4.0, name="t_fallback",
                            registry=reg, tracer=telemetry.Tracer())
    x = np.ones(3, np.float32)
    np.testing.assert_array_equal(cache(x), x * 4.0)
    assert _counter(reg, "zoo_compile_cache_misses_total", "t_fallback") == 1
    import jax
    assert not cache.warm(jax.ShapeDtypeStruct((3,), np.float32))
    assert len(cache) == 0


def test_cache_fallbacks_are_counted_and_warned_once(orca_ctx, caplog):
    """Leaving the AOT path keeps working but is never silent: every such
    dispatch counts on ``cache.fallbacks``, the first per signature logs a
    warning with the traceback."""
    import logging
    cache = ExecutableCache(lambda x: x * 4.0, name="t_count",
                            registry=telemetry.MetricsRegistry(),
                            tracer=telemetry.Tracer())
    assert cache.fallbacks == 0
    x = np.ones(3, np.float32)
    with caplog.at_level(logging.WARNING,
                         logger=compile_ahead.logger.name):
        for _ in range(3):
            np.testing.assert_array_equal(cache(x), x * 4.0)
        cache(np.ones(5, np.float32))            # a second signature
    assert cache.fallbacks == 4
    warned = [r for r in caplog.records if "t_count" in r.getMessage()]
    assert len(warned) == 2                      # once per signature
    assert all(r.exc_info for r in warned)       # with the traceback


def test_cache_counts_an_executable_that_rejects_its_call(orca_ctx):
    """An executable whose live arguments never match what it was built
    for (here: an int32 build called with float32) recompiles through jit
    on every dispatch — the result is right, the count says so."""
    import jax
    cache, reg, _ = _fresh_cache(lambda x: x + 1, "t_reject")
    x = np.ones((2, 2), np.float32)
    sig = cache.signature((x,))
    exe = jax.jit(lambda x: x + 1).lower(
        jax.ShapeDtypeStruct((2, 2), np.int32)).compile()
    with cache._lock:
        cache._execs[sig] = exe
    for n in (1, 2):
        np.testing.assert_array_equal(np.asarray(cache(x)), x + 1)
        assert cache.fallbacks == n


def test_in_band_compile_keeps_a_placed_arguments_sharding(orca_ctx):
    """A miss builds for the live arguments' shardings: built for the
    default device instead, the executable would reject a mesh-placed
    argument on every dispatch (seen with load_zoo params on four
    devices)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from analytics_zoo_tpu.parallel.mesh import get_default_mesh
    mesh = get_default_mesh()
    assert mesh.devices.size > 1
    w = jax.device_put(np.ones((4, 4), np.float32), NamedSharding(mesh, P()))
    cache, _, _ = _fresh_cache(lambda w, x: x @ w, "t_placed")
    x = np.ones((2, 4), np.float32)
    for _ in range(2):
        np.testing.assert_array_equal(np.asarray(cache(w, x)), x @ np.ones(
            (4, 4), np.float32))
    assert cache.fallbacks == 0


def test_process_exits_cleanly_during_warmup():
    """A short-lived process must not abort while a background ladder
    warmup is mid-compile: a daemon thread killed inside an XLA compile
    takes the interpreter down from C++ ('terminate called without an
    active exception'). The atexit drain in compile_ahead cancels the
    remaining rungs and joins the in-flight build."""
    src = (
        "import jax, numpy as np\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from analytics_zoo_tpu.common import compile_ahead, telemetry\n"
        "cache = compile_ahead.ExecutableCache(\n"
        "    jax.jit(lambda x: (x @ x.T).sum(-1)), name='t_exit',\n"
        "    registry=telemetry.MetricsRegistry(),\n"
        "    tracer=telemetry.Tracer())\n"
        "cache.warm_async([(jax.ShapeDtypeStruct((r, 64), np.float32),)\n"
        "                  for r in (8, 16, 32, 64, 128)])\n"
        # exit immediately, compiles still in flight
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", src], capture_output=True,
                          text=True, timeout=300, cwd=repo)
    assert proc.returncode == 0, \
        f"exit during warmup crashed ({proc.returncode}):\n{proc.stderr[-2000:]}"


def test_cache_warm_async_builds_all_rungs(orca_ctx):
    import jax
    cache, _, _ = _fresh_cache(lambda x: x.sum(axis=-1), "t_async")
    sets = [(jax.ShapeDtypeStruct((r, 3), np.float32),) for r in (2, 4, 8)]
    t = cache.warm_async(sets)
    assert isinstance(t, threading.Thread)
    t.join(60)
    assert len(cache) == 3
    for (aval,) in sets:
        assert cache.ready(aval)


def test_warm_dispatch_leaves_jit_counters_flat(orca_ctx):
    """The tentpole invariant at unit scale: an AOT-warmed signature
    dispatches through the stored executable, so the instrument_jit
    recompile counter cannot move."""
    import jax
    reg = telemetry.MetricsRegistry()
    jitted = telemetry.instrument_jit(lambda x: x @ x.T, name="t_flat",
                                      registry=reg)
    cache = ExecutableCache(jitted, name="t_flat", registry=reg,
                            tracer=telemetry.Tracer())
    aval = jax.ShapeDtypeStruct((4, 2), np.float32)
    assert cache.warm(aval)
    x = np.ones((4, 2), np.float32)
    for _ in range(3):
        cache(x)
    assert jitted.cache_misses == 0
    assert _counter(reg, "zoo_jit_calls_total", "t_flat") == 0
    assert _counter(reg, "zoo_compile_cache_hits_total", "t_flat") == 3


# --------------------------------------------- bitwise: padded vs unpadded
def _flax_im(n_in=6, n_out=4):
    import flax.linen as nn
    from analytics_zoo_tpu.inference import InferenceModel

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(n_out)(nn.relu(nn.Dense(16)(x)))

    return InferenceModel().load_flax(
        Net(), np.zeros((1, n_in), np.float32))


def _two_input_im():
    import flax.linen as nn
    import jax.numpy as jnp
    from analytics_zoo_tpu.inference import InferenceModel

    class TwoIn(nn.Module):
        @nn.compact
        def __call__(self, a, b):
            h = jnp.concatenate([a, b], axis=-1)
            return nn.Dense(3)(nn.relu(nn.Dense(8)(h)))

    sample = (np.zeros((1, 4), np.float32), np.zeros((1, 2), np.float32))
    return InferenceModel().load_flax(TwoIn(), sample)


def test_predict_padded_tail_bitwise(orca_ctx):
    """Tail chunk that doesn't divide the rung: 10 rows at batch_size=4
    pads the final 2-row chunk to rung 4 — outputs must be bitwise
    identical to the unpadded single-chunk predict."""
    im = _flax_im()
    x = np.random.default_rng(3).standard_normal((10, 6)).astype(np.float32)
    base = im.predict(x)                      # one unpadded chunk of 10
    im.set_ladder(4, 8)
    im.warm_up(block=True)
    padded = im.predict(x, batch_size=4)      # chunks 4, 4, 2->pad 4
    np.testing.assert_array_equal(base, padded)


def test_predict_padded_multi_input_bitwise(orca_ctx):
    im = _two_input_im()
    rng = np.random.default_rng(4)
    a = rng.standard_normal((11, 4)).astype(np.float32)
    b = rng.standard_normal((11, 2)).astype(np.float32)
    base = im.predict((a, b))
    im.set_ladder(4, 8)
    im.warm_up(block=True)
    padded = im.predict((a, b), batch_size=8)  # chunks 8, 3->pad rung 4
    np.testing.assert_array_equal(base, padded)


def test_serving_drain_path_padded_bitwise(orca_ctx):
    """The engine pads every drained batch to a ladder rung; results per
    record must be bitwise identical to an unpadded direct predict."""
    from analytics_zoo_tpu.serving import (
        Broker, ClusterServing, InputQueue, OutputQueue,
    )
    im = _flax_im(n_in=3, n_out=2)
    rng = np.random.default_rng(5)
    xs = {f"u{i}": rng.standard_normal(3).astype(np.float32)
          for i in range(6)}
    stacked = np.stack(list(xs.values()))
    base = np.asarray(im.predict(stacked))    # one unpadded chunk of 6
    with Broker.launch() as broker, \
            ClusterServing(im, broker.port, batch_size=8,
                           min_batch_size=8, max_batch_size=8,
                           pipeline_window=2).start() as eng:
        in_q = InputQueue(port=broker.port)
        out_q = OutputQueue(port=broker.port)
        uris = in_q.enqueue_batch((u, {"x": v}) for u, v in xs.items())
        res = out_q.query_many(uris, timeout=30.0)
        eng.wait_warm(timeout=120)   # don't leak a warm thread to the next test
    assert all(v is not None for v in res.values())
    for i, u in enumerate(xs):
        np.testing.assert_array_equal(res[u], base[i])


# -------------------------------------------------- warmup integration
def test_serving_warmup_growth_no_recompiles_no_overlap(orca_ctx):
    """ISSUE 5 acceptance at test scale: after the background ladder
    warmup, a burst that crosses at least one bucket-growth boundary
    leaves ``zoo_jit_cache_misses_total{fn=inference_model}`` flat, and
    no serve-thread span overlaps any compile span."""
    from analytics_zoo_tpu.serving import (
        Broker, ClusterServing, InputQueue, OutputQueue,
    )

    def jit_misses():
        return telemetry.snapshot().get(
            "zoo_jit_cache_misses_total", {}).get("fn=inference_model", 0.0)

    # hermetic span window: drain warmup threads other tests left behind,
    # then only consider compile spans that START inside this test
    for t in threading.enumerate():
        if t.name.startswith("zoo-warmup"):
            t.join(120)
    from time import perf_counter
    t0 = perf_counter()

    im = _flax_im(n_in=3, n_out=2)
    rng = np.random.default_rng(6)
    xs = {f"w{i}": rng.standard_normal(3).astype(np.float32)
          for i in range(96)}
    with Broker.launch() as broker, \
            ClusterServing(im, broker.port, batch_size=2,
                           min_batch_size=2, max_batch_size=8,
                           pipeline_window=2).start() as eng:
        assert eng.wait_warm(timeout=120) is eng
        for rung in eng.ladder.rungs:
            assert im.rung_ready(rung), f"rung {rung} not warm"
        # the serve loop's idle dequeue poll (<= block_ms) may already be
        # in flight while the last background compile tails off — that
        # blocked broker read is not serve work. Let one poll cycle pass
        # so every burst span starts strictly after the compiles end.
        import time
        time.sleep(0.25)
        base = jit_misses()
        in_q = InputQueue(port=broker.port)
        out_q = OutputQueue(port=broker.port)
        uris = in_q.enqueue_batch((u, {"x": v}) for u, v in xs.items())
        res = out_q.query_many(uris, timeout=60.0)
        peak = eng.batch_size
    assert all(v is not None for v in res.values())
    assert peak > 2, "burst never crossed a bucket-growth boundary"
    assert jit_misses() == base, "serve path recompiled after warmup"

    # every compile span must end before any serve-thread span of this
    # burst starts (stall-free: the serve thread never builds an exe)
    tracer = telemetry.get_tracer()
    compiles = [(s.start, s.end) for s in tracer.get(WARMUP_TRACE_ID)
                if s.start >= t0]
    assert compiles, "warmup recorded no compile spans"
    serve_spans = [s for u in xs for s in tracer.get(u)]
    assert serve_spans, "burst recorded no serving spans"
    for s in serve_spans:
        for c0, c1 in compiles:
            assert s.end <= c0 or c1 <= s.start, \
                f"serve span {s.name} overlaps a compile span"


def test_engine_idle_shrink_records_bucket(orca_ctx):
    """Satellite: sustained idle steps the bucket DOWN one rung and the
    transition lands on the batch_size timer + serving gauge."""
    from analytics_zoo_tpu.serving import ClusterServing

    class Duck:
        def predict_async(self, x):
            return np.asarray(x)

        def predict_fetch(self, pending):
            return pending

    eng = ClusterServing(Duck(), broker_port=0, batch_size=8,
                         min_batch_size=2, max_batch_size=8,
                         stream="t_shrink")
    assert eng.batch_size == 8
    for _ in range(eng.IDLE_SHRINK_AFTER):
        eng._grow_batch_on_backlog(0)         # empty polls count as idle
    assert eng.batch_size == 4                # one rung down, not a crash
    m = eng.metrics()
    assert m["batch_size"]["count"] >= 1
    snap = telemetry.snapshot()
    assert snap["zoo_serving_batch_bucket"]["stream=t_shrink"] == 4
    # shrink floors at min_batch_size
    for _ in range(2 * eng.IDLE_SHRINK_AFTER):
        eng._grow_batch_on_backlog(0)
    assert eng.batch_size == 2
    for _ in range(2 * eng.IDLE_SHRINK_AFTER):
        eng._grow_batch_on_backlog(0)
    assert eng.batch_size == 2
