"""Smoke coverage for the driver contracts: bench.py must emit its one
JSON line and __graft_entry__.entry() must stay jittable — a breakage in
either costs the round's BENCH/MULTICHIP artifacts."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _flight_dumps_to_tmp(monkeypatch, tmp_path):
    # keep flight-recorder postmortems out of the repo's zoo_tpu_logs/
    monkeypatch.setenv("ZOO_FLIGHT_RECORDER_DIR", str(tmp_path))


@pytest.fixture
def tiny_bench(monkeypatch):
    import bench
    monkeypatch.setattr(bench, "N_ROWS", 4000)
    monkeypatch.setattr(bench, "BATCH", 512)
    monkeypatch.setattr(bench, "WARMUP_STEPS", 2)
    monkeypatch.setattr(bench, "MEASURE_STEPS", 4)
    monkeypatch.setattr(bench, "STEPS_PER_LOOP", 2)
    return bench


def test_measure_ncf_both_paths(tiny_bench, orca_ctx):
    res = tiny_bench.measure_ncf()
    assert res["staged"] > 0
    assert res["best"] >= res["staged"]
    # 8 virtual devices → no single-device cached measurement
    if res["cached"] is not None:
        assert res["cached"] > 0


@pytest.mark.slow  # ~11s: trains the TCN bench model on 1 core
def test_measure_tcn(tiny_bench, orca_ctx):
    out = tiny_bench.measure_tcn()
    assert out["tcn_steps_per_sec"] > 0


def test_measure_serving(tiny_bench, orca_ctx, monkeypatch):
    monkeypatch.setattr(tiny_bench, "SERVE_N", 96)
    monkeypatch.setattr(tiny_bench, "SERVE_BATCH", 16)
    monkeypatch.setattr(tiny_bench, "SERVE_HIDDEN", 32)
    monkeypatch.setattr(tiny_bench, "SERVE_WINDOW", 2)
    monkeypatch.setattr(tiny_bench, "SERVE_REPS", 1)
    out = tiny_bench.measure_serving()
    # the sync-vs-pipelined pair is the ISSUE 1 artifact; the headline
    # key stays for dashboard continuity (== the pipelined number)
    assert out["serving_sync_records_per_sec"] > 0
    assert out["serving_pipelined_records_per_sec"] > 0
    assert (out["serving_records_per_sec"]
            == out["serving_pipelined_records_per_sec"])
    assert out["serving_pipeline_speedup"] > 0
    assert out["serving_broker"] in ("native", "python")


def test_step_flops_helper(tiny_bench, orca_ctx):
    """cost_analysis plumbing (the MFU numerator) works on this backend."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(a, b):
        return a @ b

    flops = None
    try:
        compiled = f.lower(jnp.ones((64, 64)), jnp.ones((64, 64))).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0))
    except Exception:
        pytest.skip("cost_analysis unavailable on this backend")
    assert flops and flops >= 2 * 64 * 64 * 64 * 0.5


def test_entry_is_jittable(orca_ctx):
    import jax
    import __graft_entry__

    fn, args = __graft_entry__.entry()
    out = jax.jit(fn)(*args)
    assert jax.tree_util.tree_leaves(out)[0].shape[0] == 8


@pytest.mark.slow  # ~29s: compiles the BERT step across the batch sweep
def test_measure_bert_sweep(tiny_bench, orca_ctx, monkeypatch):
    """measure_bert emits the canonical-batch detail plus the MFU sweep
    (tiny model/batches so the smoke stays fast on CPU)."""
    monkeypatch.setattr(tiny_bench, "BERT_SEQ", 16)
    monkeypatch.setattr(tiny_bench, "BERT_BATCHES", (8, 16))
    monkeypatch.setattr(tiny_bench, "BERT_SCAN_STEPS", 2)
    monkeypatch.setattr(tiny_bench, "BERT_CFG_KW",
                        dict(vocab=100, hidden_size=32, n_block=2,
                             n_head=2, intermediate_size=64,
                             max_position_len=32))
    out = tiny_bench.measure_bert()
    assert out["bert_step_ms"] > 0
    assert out["bert_scan_step_ms"] > 0
    assert set(out["bert_mfu_sweep"]) == {"8", "16"}
    # no peak table entry for the CPU device → MFU fields None or absent
    if out.get("bert_base_mfu") is not None:
        assert 0 < out["bert_base_mfu"] <= 1.5


def test_measure_flash_attention(tiny_bench, orca_ctx, monkeypatch):
    bench = tiny_bench
    monkeypatch.setattr(bench, "FA_BATCH", 1)
    monkeypatch.setattr(bench, "FA_SEQ", 128)
    monkeypatch.setattr(bench, "FA_HEADS", 2)
    monkeypatch.setattr(bench, "FA_DIM", 32)
    monkeypatch.setattr(bench, "FA_ITERS", 2)
    out = bench.measure_flash_attention()
    assert out["blockwise_attn_seq_ms"] > 0
    # on the CPU mesh pallas is unavailable: the fn must still return the
    # blockwise number plus the reason (on chip this key is the speedup)
    assert "flash_vs_blockwise_speedup" in out or "flash_attn_error" in out


def test_measure_int8_predict(tiny_bench, orca_ctx, monkeypatch):
    bench = tiny_bench
    monkeypatch.setattr(bench, "INT8_MODEL", "resnet-lite")
    monkeypatch.setattr(bench, "INT8_IMAGE", 32)
    monkeypatch.setattr(bench, "INT8_BATCH", 4)
    monkeypatch.setattr(bench, "INT8_CLASSES", 5)
    monkeypatch.setattr(bench, "INT8_ITERS", 2)
    out = bench.measure_int8_predict()
    assert out["resnet50_fp32_ms_per_batch32"] > 0
    assert out["resnet50_int8_speedup"] > 0
    assert out["ncf_int8_speedup"] > 0


def test_smoke_mode_embeds_telemetry_snapshot(tiny_bench, monkeypatch,
                                              capsys):
    """``bench.py --smoke`` must print the one-line JSON record with the
    telemetry snapshot riding along (ISSUE 2: the BENCH line is
    self-describing — recompiles, transfer bytes, stage times)."""
    from analytics_zoo_tpu.common import telemetry

    bench = tiny_bench
    telemetry.reset_for_tests()

    def fake_ncf():
        # what the real measures do: report through the registry
        telemetry.get_registry().counter(
            "zoo_jit_cache_misses_total", labelnames=("fn",)).labels(
            "bench_stub").inc(3)
        return {"best": 9.0, "staged": 9.0, "cached": None}

    def fake_serving():
        telemetry.get_tracer().record("bench-uri", "serve", 0.0, 0.01)
        return {"serving_records_per_sec": 5.0}

    # SERVE_*/RECSYS_* restored by monkeypatch even though _smoke assigns
    # globals
    for k in ("SERVE_N", "SERVE_BATCH", "SERVE_HIDDEN", "SERVE_WINDOW",
              "SERVE_REPS", "RECSYS_ROWS", "RECSYS_SHARDS", "RECSYS_USERS",
              "RECSYS_ITEMS", "RECSYS_BATCH"):
        monkeypatch.setattr(bench, k, getattr(bench, k))
    monkeypatch.setattr(bench, "measure_ncf", fake_ncf)
    monkeypatch.setattr(bench, "measure_serving", fake_serving)
    # the replica drills spawn subprocess fleets — covered by
    # test_multi_replica.py and the chaos lane, stubbed out here; the
    # recsys pipeline measure has its own focused test below
    for heavy in ("measure_serving_failover", "measure_serving_multi_replica",
                  "measure_replica_kill_failover",
                  "measure_recsys_pipeline"):
        monkeypatch.setattr(bench, heavy, lambda: {})
    bench._smoke()
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["mode"] == "smoke"
    assert rec["value"] == 9.0
    assert rec["serving_records_per_sec"] == 5.0
    snap = rec["telemetry"]
    assert snap["zoo_jit_cache_misses_total"]["fn=bench_stub"] == 3
    assert snap["trace_ids_held"] >= 1
    json.dumps(snap)  # the whole snapshot stays JSON-able


def test_assemble_record_reports_telemetry_failure_softly(tiny_bench,
                                                          monkeypatch):
    """A broken snapshot must not kill the BENCH line (one failure, one
    error field — which ``main()`` then turns into a non-zero exit)."""
    from analytics_zoo_tpu.common import telemetry
    bench = tiny_bench
    monkeypatch.setattr(
        bench, "measure_ncf",
        lambda: {"best": 1.0, "staged": 1.0, "cached": None})
    monkeypatch.setattr(telemetry, "bench_snapshot",
                        lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    rec = bench._assemble_record({"metric": "x"}, ())
    assert "telemetry" not in rec
    assert "boom" in rec["telemetry_error"]
    assert rec["value"] == 1.0


def test_measure_resnet50_train(tiny_bench, orca_ctx, monkeypatch):
    bench = tiny_bench
    monkeypatch.setattr(bench, "RN50_MODEL", "resnet-lite")
    monkeypatch.setattr(bench, "RN50_IMAGE", 32)
    monkeypatch.setattr(bench, "RN50_BATCH", 8)
    monkeypatch.setattr(bench, "RN50_ITERS", 2)
    out = bench.measure_resnet50_train()
    assert out["resnet50_train_samples_per_sec"] > 0
    assert out["resnet50_train_step_ms"] > 0


def test_measure_widedeep_train(tiny_bench, orca_ctx, monkeypatch):
    bench = tiny_bench
    monkeypatch.setattr(bench, "WND_BATCH", 16)
    monkeypatch.setattr(bench, "WND_ITERS", 2)
    monkeypatch.setattr(bench, "WND_DIMS", dict(
        wide_base=(4, 6), wide_cross=(10,), indicator=(3, 2),
        embed_in=(5, 7), embed_out=(3, 4), n_continuous=2))
    out = bench.measure_widedeep_train()
    assert out["widedeep_train_samples_per_sec"] > 0


def test_measure_recsys_pipeline(tiny_bench, orca_ctx, monkeypatch):
    """ISSUE 12 gate: full Friesian data plane → streaming feed → NCF fit,
    data time included, with the never-slower transform dispatch."""
    bench = tiny_bench
    monkeypatch.setattr(bench, "RECSYS_ROWS", 1200)
    monkeypatch.setattr(bench, "RECSYS_SHARDS", 4)
    monkeypatch.setattr(bench, "RECSYS_USERS", 50)
    monkeypatch.setattr(bench, "RECSYS_ITEMS", 40)
    monkeypatch.setattr(bench, "RECSYS_BATCH", 128)
    out = bench.measure_recsys_pipeline()
    assert out["recsys_pipeline_samples_per_sec"] > 0
    assert out["recsys_pipeline_rows"] > 0
    # never-slower dispatch: the higher-better *_speedup gate metric can
    # never sit below par — the pipeline runs whichever mode measured
    # faster
    assert out["friesian_transform_speedup"] >= 1.0
    assert out["recsys_transform_mode"] in ("vectorized-parallel",
                                            "legacy-serial")


# ---------------------------------------------- the full run's exit contract

class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def _full_run(bench, monkeypatch, parts_result, platform="tpu"):
    """Drive ``bench.main()`` (no flag: the full run) with stubbed parts."""
    import jax

    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setattr(
        jax, "devices", lambda *a: [_FakeDevice(platform, "TPU v5 lite")])
    monkeypatch.setattr(bench, "_device_sanity", lambda out: None)
    monkeypatch.setattr(
        bench, "measure_ncf",
        lambda: {"best": 7.0, "staged": 7.0, "cached": None})
    for name in [n for n in dir(bench) if n.startswith("measure_")
                 and n != "measure_ncf"]:
        monkeypatch.setattr(bench, name, parts_result.get(name, lambda: {}))
    try:
        bench.main()
    finally:
        # main() arms the flight recorder's SIGTERM handler
        from analytics_zoo_tpu.common import profiling
        profiling.get_flight_recorder().disarm()


def test_full_run_refuses_without_a_tpu(tiny_bench, monkeypatch, capsys):
    """No chip, no record: the full run exits non-zero naming the platform
    it found and prints no JSON line (``--smoke`` is the CPU lane)."""
    with pytest.raises(SystemExit) as exc:
        _full_run(tiny_bench, monkeypatch, {}, platform="cpu")
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)
    assert capsys.readouterr().out.strip() == ""


def test_full_run_exits_nonzero_on_a_part_error(tiny_bench, monkeypatch,
                                                capsys):
    """A part that raises leaves its ``*_error`` key on the line AND the
    run fails: a record with a hole in it is not a clean run."""
    def boom():
        raise RuntimeError("kaput")

    boom.__name__ = "measure_tcn"
    with pytest.raises(SystemExit) as exc:
        _full_run(tiny_bench, monkeypatch, {"measure_tcn": boom})
    assert exc.value.code not in (0, None)
    assert "measure_tcn_error" in str(exc.value.code)
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "kaput" in rec["measure_tcn_error"]
    assert rec["value"] == 7.0          # what was measured still rides


def test_full_run_clean_exits_zero(tiny_bench, monkeypatch, capsys):
    _full_run(tiny_bench, monkeypatch, {})
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["device"] == "TPU v5 lite" and rec["value"] == 7.0
    assert not [k for k in rec if k.endswith("_error")]


def test_bench_has_no_cpu_fallback_left():
    import bench
    for gone in ("_cpu_fallback_line", "_emit_cpu_fallback_and_exit",
                 "_cpu_emit", "_device_watchdog", "_run_with_deadline"):
        assert not hasattr(bench, gone), gone
    with open(bench.__file__) as fh:
        assert "--cpu-emit" not in fh.read()
