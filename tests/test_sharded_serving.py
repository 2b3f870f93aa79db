"""Sharded model-executor seam (ISSUE 14).

conftest forces ``--xla_force_host_platform_device_count=8``, so every
test here runs against a real 8-device mesh: ShardedExecutable dispatch
must be numerically equivalent to the unsharded apply, the per-shard HBM
accounting must prove no single device holds the whole model, a warmed
sharded `InferenceModel` must dispatch every rung with ZERO recompiles
(the sharded-aval fix), the fleet metrics merge must NOT sum shard-
labeled resource gauges, and one end-to-end generate request must flow
client → lanes → assembly → sharded prefill → decode loop → typed
result with decode spans on ``GET /trace``.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import flax.linen as nn

from analytics_zoo_tpu.common import compile_ahead, telemetry
from analytics_zoo_tpu.inference import InferenceModel
from analytics_zoo_tpu.parallel.sharded_executable import ShardedExecutable

# tensor-parallel rules: Dense kernels split on the output-feature axis,
# biases (no match) replicate
RULES = [(r"kernel", (None, "model"))]


class _Net(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Dense(32)(x))
        return nn.Dense(8)(x)


def _jit_misses() -> float:
    fam = telemetry.snapshot().get("zoo_jit_cache_misses_total", {})
    if not isinstance(fam, dict):
        return float(fam or 0.0)
    return float(fam.get("fn=inference_model", 0.0))


def _in_band_compiles() -> float:
    """Dispatches that found no executable built ahead of time."""
    fam = telemetry.snapshot().get("zoo_compile_cache_misses_total", {})
    return float(fam.get("fn=inference_model", 0.0))


def _net_and_params():
    net = _Net()
    params = net.init(jax.random.PRNGKey(0),
                      np.zeros((1, 16), np.float32))
    return net, params


# ------------------------------------------------- ShardedExecutable

def test_mesh_is_eight_devices():
    assert len(jax.devices()) == 8     # the whole file depends on this


def test_sharded_dispatch_matches_unsharded():
    net, params = _net_and_params()
    ex = ShardedExecutable(lambda p, x: net.apply(p, x), params,
                           "tp8", param_rules=RULES)
    assert ex.n_shards == 8
    xb = np.random.RandomState(1).randn(4, 16).astype(np.float32)
    ref = np.asarray(net.apply(params, xb))
    np.testing.assert_allclose(np.asarray(ex(xb)), ref,
                               rtol=1e-5, atol=1e-5)


def test_shard_hbm_proves_no_device_holds_whole_model():
    net, params = _net_and_params()
    ex = ShardedExecutable(lambda p, x: net.apply(p, x), params,
                           "tp8", param_rules=RULES)
    hbm = ex.shard_hbm_bytes()
    total = ex.total_param_bytes()
    assert len(hbm) == 8 and total > 0
    # kernels are split 8-way: the largest shard is a fraction of the
    # model, while replicated biases keep the sum at or above the total
    assert max(hbm.values()) < total
    assert sum(hbm.values()) >= total
    fam = telemetry.snapshot().get("zoo_shard_hbm_bytes", {})
    assert isinstance(fam, dict)
    assert any(k.startswith("shard=") for k in fam)


def test_replicated_params_without_rules():
    net, params = _net_and_params()
    ex = ShardedExecutable(lambda p, x: net.apply(p, x), params, "tp8")
    hbm = ex.shard_hbm_bytes(publish=False)
    # no rules matched → every shard holds the full model (the failure
    # mode the strict-fraction assertions of this file exist to catch)
    assert max(hbm.values()) == ex.total_param_bytes()


def test_warm_rungs_dispatch_without_recompile():
    net, params = _net_and_params()
    ex = ShardedExecutable(lambda p, x: net.apply(p, x), params,
                           "tp8", param_rules=RULES, name="warm_rung_test")
    spec = (((16,), np.dtype(np.float32)),)
    ex.warm(spec, (2, 4, 8), block=True)
    for rung in (2, 4, 8):
        out = ex(np.zeros((rung, 16), np.float32))
        assert np.asarray(out).shape == (rung, 8)


# --------------------------------------------- InferenceModel seam

def test_inference_model_shard_matches_unsharded():
    net, params = _net_and_params()
    x0 = np.zeros((1, 16), np.float32)
    plain = InferenceModel().load_flax(net, x0, params=params)
    sharded = InferenceModel().load_flax(net, x0, params=params)
    sharded.shard("tp8", param_rules=RULES)
    info = sharded.shard_info()
    assert info["n_shards"] == 8
    assert max(info["shard_hbm_bytes"].values()) \
        < info["total_param_bytes"]
    xb = np.random.RandomState(3).randn(5, 16).astype(np.float32)
    np.testing.assert_allclose(np.asarray(sharded.predict(xb)),
                               np.asarray(plain.predict(xb)),
                               rtol=1e-5, atol=1e-5)


def test_sharded_warm_ladder_dispatches_recompile_flat():
    """Satellite pin: warmup builds every rung from SHARDED avals, so
    plain numpy batches (tail lengths included) hit the AOT executables
    and ``zoo_jit_cache_misses_total{fn=inference_model}`` stays flat."""
    net, params = _net_and_params()
    im = InferenceModel().load_flax(net, np.zeros((1, 16), np.float32),
                                    params=params)
    im.shard("tp8", param_rules=RULES)
    im.set_ladder(compile_ahead.BucketLadder(2, 8))
    im.warm_up(block=True)
    base = _jit_misses()
    rng = np.random.RandomState(2)
    for n in (2, 3, 4, 5, 8):           # tails pad up to warmed rungs
        out = im.predict(rng.randn(n, 16).astype(np.float32))
        assert np.asarray(out).shape == (n, 8)
    assert _jit_misses() == base


def test_sharded_engine_burst_crosses_a_rung_without_recompiling():
    """A tensor-parallel model behind the engine: every device holds a
    strict fraction of it, warm-up walks the ladder with sharded avals,
    and a burst deep enough to step the bucket up is answered whole
    through executables built ahead of time."""
    from analytics_zoo_tpu.serving import (
        Broker, ClusterServing, InputQueue, OutputQueue,
    )

    net, params = _net_and_params()
    im = InferenceModel().load_flax(net, np.zeros((1, 16), np.float32),
                                    params=params)
    im.shard("tp8", param_rules=RULES)
    info = im.shard_info()
    assert info["n_shards"] == 8
    assert 0 < max(info["shard_hbm_bytes"].values()) \
        < info["total_param_bytes"]
    rng = np.random.RandomState(21)
    # dequeues at the bottom rung come back full well past the engine's
    # grow-after streak
    xs = {f"sh{i}": rng.randn(16).astype(np.float32) for i in range(48)}
    with Broker.launch() as broker, \
            ClusterServing(im, broker.port, batch_size=2, min_batch_size=2,
                           max_batch_size=8, pipeline_window=2) as eng:
        eng.wait_warm(timeout=240.0)
        base = _jit_misses() + _in_band_compiles()
        uris = InputQueue(port=broker.port).enqueue_batch(
            (u, {"x": v}) for u, v in xs.items())
        res = OutputQueue(port=broker.port).query_many(uris, timeout=120.0)
        peak = eng.batch_size
    assert all(v is not None for v in res.values())
    assert peak > 2, "burst never crossed a bucket-growth boundary"
    assert _jit_misses() + _in_band_compiles() == base, \
        "the burst met a rung warm-up had not built"
    ref = np.asarray(net.apply(params, np.stack(list(xs.values()))))
    np.testing.assert_allclose(np.stack([res[u] for u in xs]), ref,
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------ fleet merge

def test_fleet_merge_does_not_sum_shard_gauges():
    """Satellite pin: identically-labeled ``zoo_shard_hbm_bytes`` series
    from different replicas describe the SAME resident parameters — the
    fleet scope must merge them by max, never sum, while counters keep
    adding."""
    a = {"zoo_shard_hbm_bytes": {"shard=0": 100.0, "shard=1": 80.0},
         "zoo_serving_requests_total": 5.0}
    b = {"zoo_shard_hbm_bytes": {"shard=0": 100.0, "shard=1": 90.0},
         "zoo_serving_requests_total": 7.0}
    merged = telemetry.MetricsRegistry.merge_snapshot(a, b)
    assert merged["zoo_shard_hbm_bytes"]["shard=0"] == 100.0
    assert merged["zoo_shard_hbm_bytes"]["shard=1"] == 90.0
    assert merged["zoo_serving_requests_total"] == 12.0
    # the unlabeled KV-rung gauge is non-additive too: two replicas at
    # rung 16 and 8 are a fleet at rung 16, not a fleet at rung 24
    assert telemetry.MetricsRegistry.merge_snapshot(
        {"zoo_kv_cache_rung": 16.0},
        {"zoo_kv_cache_rung": 8.0})["zoo_kv_cache_rung"] == 16.0


# -------------------------------------------------- end-to-end flow

@pytest.mark.parametrize("steps", [16])
def test_serving_generate_end_to_end(steps):
    """Acceptance drill: a generate request (prefill + >= 16 decode
    steps) flows client → lanes → assembly → sharded prefill → decode
    loop → typed ``[steps, dim]`` result, with decode-step spans on
    ``GET /trace`` and the sharding block on ``/healthz``."""
    from analytics_zoo_tpu.models import Seq2Seq
    from analytics_zoo_tpu.serving import (
        Broker, ClusterServing, FrontEnd, InputQueue, OutputQueue,
    )

    m = Seq2Seq(input_dim=3, output_dim=2, hidden_size=8, rnn_type="gru",
                encoder_seq_len=5, decoder_seq_len=4)
    im = InferenceModel().load_zoo(m)
    im.shard("tp2")                     # dp4 x tp2 over the 8 devices
    rng = np.random.RandomState(0)
    enc = rng.randn(5, 3).astype(np.float32)
    start = np.zeros(2, np.float32)

    b = Broker.launch(backend="python")
    eng = ClusterServing(im, b.port, batch_size=4, warmup=False)
    eng.start()
    fe = FrontEnd(b.port, engine=eng).start()
    try:
        in_q = InputQueue(port=b.port)
        out_q = OutputQueue(port=b.port)
        uri = in_q.enqueue("e2e_gen",
                           generate={"max_new_tokens": steps,
                                     "mode": "raw"},
                           x=enc, start=start)
        res = out_q.query(uri, timeout=90.0)
        assert res is not None and res.shape == (steps, 2)
        ref = im.generate(enc[None], start[None], steps, mode="raw")
        np.testing.assert_allclose(res, ref[0], rtol=1e-5, atol=1e-5)

        # a plain predict record runs alongside unharmed
        uri2 = in_q.enqueue("e2e_plain", a_enc=enc,
                            b_dec=np.zeros((4, 2), np.float32))
        res2 = out_q.query(uri2, timeout=60.0)
        assert res2 is not None and res2.shape == (4, 2)

        # decode-step spans visible on the trace endpoint
        with urllib.request.urlopen(
                f"http://127.0.0.1:{fe.port}/trace?uri=e2e_gen") as r:
            tr = json.loads(r.read())
        names = [str(e.get("name", "")) for e in tr.get("traceEvents", [])]
        n_spans = sum(1 for n in names if n.startswith("decode_step_"))
        assert n_spans >= steps, names

        # /healthz carries the per-shard HBM block (an SLO shed in this
        # tiny run answers 503 but the body is still the full document)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{fe.port}/healthz") as r:
                hz = json.loads(r.read())
        except urllib.error.HTTPError as e:
            hz = json.loads(e.read())
        sharding = hz.get("sharding") or {}
        assert sharding.get("n_shards") == 8
        assert sharding.get("shard_hbm_bytes")
    finally:
        fe.stop()
        eng.stop()
        b.stop()


def test_generate_request_validation():
    from analytics_zoo_tpu.serving import schema
    assert schema.validate_generate(None) is None
    assert schema.validate_generate({}) == {"n": 16}
    g = schema.validate_generate({"max_new_tokens": 8, "mode": "sample",
                                  "temperature": 0.5, "seed": 3})
    assert g == {"n": 8, "m": "sample", "t": 0.5, "s": 3}
    with pytest.raises(ValueError):
        schema.validate_generate({"mode": "beam"})
    with pytest.raises(ValueError):
        schema.validate_generate({"max_new_tokens": 0})
    with pytest.raises(ValueError):
        schema.validate_generate({"bogus": 1})
    with pytest.raises(ValueError):
        schema.validate_generate("greedy")


def test_arrow_wire_format_rejects_generate():
    from analytics_zoo_tpu.serving.client import InputQueue
    # no broker needed: validation happens before any socket write
    q = InputQueue.__new__(InputQueue)
    q.arrow, q.cipher, q.stream = True, None, "s"
    q._tracer = telemetry.get_tracer()
    with pytest.raises(ValueError):
        q._encode("u1", {"x": np.zeros(3, np.float32)},
                  generate={"max_new_tokens": 4})
