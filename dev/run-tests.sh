#!/usr/bin/env bash
# Test runner (ref pyzoo/dev/run-pytests: suite sharding per heavy
# dependency set). One env here — jax+torch coexist — so sharding is by
# subsystem for parallel CI lanes and fail isolation; every lane runs on
# the virtual 8-device CPU mesh (tests/conftest.py).
#
#   dev/run-tests.sh              # everything
#   dev/run-tests.sh core         # one lane
#   dev/run-tests.sh smoke        # fast pre-push subset (<5 min, 1 core)
#   Lanes: smoke core data keras models zouwu automl serving interop
#          examples telemetry fleet resilience zoolint kernels chaos
#          scheduling sharded decode observability
set -euo pipefail
cd "$(dirname "$0")/.."

lane="${1:-all}"

run() { echo "== pytest $*"; python -m pytest -q "$@"; }

# zoolint: AST-based static analysis (docs/zoolint.md) — hot-path
# wall-clock/sync, jit recompile hazards, unlocked cross-thread writes,
# metric/env-var catalog drift. Replaces the old time.time() grep: the
# shipped tree must be clean (modulo dev/zoolint-baseline.json and
# inline "# zoolint: disable=RULE"), and the seeded-violation fixture
# must FAIL — a passing fixture means the linter itself regressed.
lint_zoolint() {
  echo "== zoolint: analytics_zoo_tpu (interprocedural + dataflow passes)"
  python -m analytics_zoo_tpu.analysis analytics_zoo_tpu --timing
  echo "== zoolint: stale-baseline check (warning only)"
  python -m analytics_zoo_tpu.analysis analytics_zoo_tpu --prune-baseline
  echo "== zoolint: seeded-violation fixture (must fail)"
  if fixture_out="$(python -m analytics_zoo_tpu.analysis --no-baseline \
       tests/fixtures/zoolint 2>&1)"; then
    echo "zoolint passed the seeded-violation fixture — linter regressed" >&2
    exit 1
  fi
  # every whole-program / path-sensitive rule must fire on its seeded
  # fixture by id — a non-zero exit from the per-file rules alone is
  # not good enough
  for rule in cross-thread-unlocked-state lock-order-inversion \
              blocking-under-lock thread-leak \
              record-ack-leak lock-release-path span-pairing \
              tainted-host-sync shape-dependent-branch-in-jit \
              kv-page-leak; do
    if ! grep -q "$rule" <<<"$fixture_out"; then
      echo "zoolint fixture never tripped $rule — rule regressed" >&2
      exit 1
    fi
  done
  # the workflow-annotation format must carry the new findings too
  gh_out="$(python -m analytics_zoo_tpu.analysis --no-baseline \
       --format=github tests/fixtures/zoolint 2>&1 || true)"
  for rule in record-ack-leak tainted-host-sync; do
    if ! grep -q "^::error .*$rule" <<<"$gh_out"; then
      echo "zoolint --format=github lost the $rule annotation" >&2
      exit 1
    fi
  done
  echo "== zoolint: docs/concurrency.md drift check"
  owndir="$(mktemp -d)"
  python -m analytics_zoo_tpu.analysis analytics_zoo_tpu \
    --ownership-report "$owndir/concurrency.md" >/dev/null
  if ! diff -q docs/concurrency.md "$owndir/concurrency.md" >/dev/null || \
     ! diff -q docs/concurrency.json "$owndir/concurrency.json" >/dev/null; then
    echo "docs/concurrency.md is stale — regenerate with:" >&2
    echo "  python -m analytics_zoo_tpu.analysis analytics_zoo_tpu \\" >&2
    echo "    --ownership-report docs/concurrency.md" >&2
    exit 1
  fi
  rm -rf "$owndir"
}

case "$lane" in
  lint)     lint_zoolint ;;
  zoolint)  lint_zoolint ;;
  # fast cross-subsystem sweep for the edit loop: serving end-to-end,
  # the dispatch pipeline, estimator, inference + quantize, attention
  # ops — everything marked slow stays out
  smoke)    lint_zoolint
            run -m "not slow" tests/test_pipeline_io.py \
                tests/test_serving.py tests/test_inference_net.py \
                tests/test_estimator.py tests/test_attention.py ;;
  core)     run tests/test_context.py tests/test_estimator.py \
                tests/test_estimator_edge.py tests/test_estimator_factories.py \
                tests/test_attention.py tests/test_pipeline.py tests/test_moe.py ;;
  # data plane (ISSUE 12): pooled shard executor, vectorized Friesian
  # kernels with bitwise legacy parity, tiered bounded-residency
  # pipeline, streaming prefetch, the whole recsys chain into a fit
  # (docs/data_plane.md)
  data)     run tests/test_data.py tests/test_native_store.py \
                tests/test_feature.py tests/test_friesian.py \
                tests/test_friesian_parity.py tests/test_data_plane.py \
                tests/test_image3d_parquet.py tests/test_elastic_search.py \
                tests/test_tfrecord.py ;;
  keras)    run tests/test_keras.py tests/test_keras_layers_golden.py \
                tests/test_keras2_multihost.py tests/test_nnframes_autograd.py ;;
  models)   run tests/test_model_zoo.py tests/test_recommendation.py \
                tests/test_text_bert.py tests/test_gan.py ;;
  zouwu)    run tests/test_zouwu.py tests/test_autots.py \
                tests/test_stats_forecast.py ;;
  automl)   run tests/test_automl.py ;;
  serving)  run tests/test_serving.py tests/test_inference_net.py \
                tests/test_onnx.py tests/test_openvino.py \
                tests/test_encryption.py ;;
  interop)  run tests/test_inference_net.py tests/test_onnx.py \
                tests/test_openvino.py ;;
  examples) run tests/test_examples.py ;;
  # observability: registry, tracer, step profiler, and the armed
  # flight recorder's postmortem (the dump path after a wedged run)
  telemetry) lint_zoolint
            run -m "not slow" tests/test_telemetry.py tests/test_profiling.py ;;
  # pallas kernels + autotuner (ISSUE 8): flash/embedding-bag parity on
  # the CPU interpreter, then a smoke proving the autotune dispatch NEVER
  # picks a config slower than the numerics-reference fallback — the
  # invariant that turns a kernel regression into a fallback, not a perf
  # bug (lint first: new kernels must be zoolint-clean, and the catalog
  # cross-check must know the zoo_autotune_* metrics)
  kernels)  lint_zoolint
            run -m "not slow" tests/test_autotune.py \
                tests/test_embedding_bag.py tests/test_attention.py \
                tests/test_paged_attention.py
            echo "== autotune never-slower smoke"
            JAX_PLATFORMS=cpu ZOO_PALLAS_INTERPRET=1 python - <<'PY'
import os, tempfile
os.environ["ZOO_AUTOTUNE_CACHE"] = os.path.join(tempfile.mkdtemp(),
                                                "autotune.json")
os.environ["ZOO_AUTOTUNE_ITERS"] = "2"
import jax.numpy as jnp
from analytics_zoo_tpu.ops import autotune
rec = autotune.tune_attention(1, 64, 2, 64, dtype=jnp.float32,
                              causal=True)
assert rec["best"] is not None, rec["errors"]
# the dispatch invariant: the kernel only engages when its measured time
# BEAT the blockwise reference — use_kernel=True with best>=reference
# would mean the autotuner can select a slower config
if rec["use_kernel"]:
    assert rec["best_ms"] < rec["reference_ms"], rec
else:
    assert rec["best_ms"] >= rec["reference_ms"], rec
print(f"autotune OK: best={rec['best']} {rec['best_ms']}ms "
      f"ref={rec['reference_ms']}ms use_kernel={rec['use_kernel']}")
PY
            ;;
  # fleet observability (ISSUE 6): snapshot merge algebra, replica
  # registry + SLO burn units, and the two-replica federation smoke
  # (subprocess engines, one broker, merged /metrics?scope=fleet). The
  # seeded race fixture must trip the whole-program ownership rule: a
  # heartbeater-style helper-method write the per-file rule can't see.
  fleet)    run -m "not slow" tests/test_fleet.py
            echo "== zoolint: seeded heartbeater race must fire"
            drift="$(python -m analytics_zoo_tpu.analysis --no-baseline \
                       tests/fixtures/zoolint 2>&1 || true)"
            if ! grep "cross-thread-unlocked-state" <<<"$drift" | \
                 grep -q "fleet/bad_shared_state.py"; then
              echo "ownership rule missed the seeded heartbeater race" >&2
              exit 1
            fi
            ;;
  # wedge resilience (ISSUE 7): fault injector, backend supervisor
  # (one backend-wedged postmortem an episode), checkpoint fallback, fit
  # auto-resume, serving failover onto the CPU rungs and back
  resilience) run -m "not slow" tests/test_resilience.py ;;
  # multi-replica delivery contract (ISSUE 9): lease/XCLAIM semantics on
  # both broker backends, client reconnect retry, orphan detection, and
  # the 2-replica SIGKILL chaos drill (slow-marked, runs here). The
  # seeded zoolint fixture must flag an undeclared zoo_serving_* family:
  # a quiet drift check on the new delivery metrics means the linter
  # regressed, not that the tree is clean.
  chaos)    run tests/test_multi_replica.py
            echo "== zoolint: drift must flag undeclared zoo_serving_* names"
            drift="$(python -m analytics_zoo_tpu.analysis --no-baseline \
                       tests/fixtures/zoolint 2>&1 || true)"
            if ! grep -q "zoo_serving_redelivered_bogus_total" <<<"$drift"; then
              echo "catalog drift missed the seeded zoo_serving_* violation" >&2
              exit 1
            fi
            # the chaos drills' kill paths hang on leaked non-daemon
            # threads — the seeded leak must trip the lifecycle rule
            if ! grep "thread-leak" <<<"$drift" | \
                 grep -q "chaos/bad_thread_leak.py"; then
              echo "zoolint missed the seeded non-daemon thread leak" >&2
              exit 1
            fi
            ;;
  # SLO-aware continuous batching (ISSUE 10): priority lanes on both
  # broker backends, weighted-deficit scheduling, deadline expiry,
  # admission control, the lane/lease SIGKILL drill (slow-marked, runs
  # here). The seeded zoolint fixture must flag an undeclared per-lane
  # metric: a quiet drift check on the scheduling metrics means the
  # linter regressed, not that the tree is clean.
  scheduling) run tests/test_priority.py
            echo "== zoolint: drift must flag undeclared lane metrics/knobs"
            drift="$(python -m analytics_zoo_tpu.analysis --no-baseline \
                       tests/fixtures/zoolint 2>&1 || true)"
            if ! grep -q "zoo_serving_lane_depth_bogus" <<<"$drift"; then
              echo "catalog drift missed the seeded per-lane metric" >&2
              exit 1
            fi
            if ! grep -q "ZOO_SERVING_MAX_WAIT_BOGUS_MS" <<<"$drift"; then
              echo "catalog drift missed the seeded scheduling env var" >&2
              exit 1
            fi
            # a scheduler sleeping under a contended lock stalls every
            # submitter; a cross-file ABBA pair deadlocks under load —
            # both seeded races must trip the whole-program lock rules
            if ! grep "blocking-under-lock" <<<"$drift" | \
                 grep -q "scheduling/bad_blocking.py"; then
              echo "zoolint missed the seeded sleep-under-lock" >&2
              exit 1
            fi
            if ! grep "lock-order-inversion" <<<"$drift" | \
                 grep -q "scheduling/"; then
              echo "zoolint missed the seeded cross-file lock inversion" >&2
              exit 1
            fi
            ;;
  # sharded executor seam + bucketed decode (ISSUE 14): dispatch
  # equivalence and recompile-flat warm rungs on the forced 8-device
  # mesh, bitwise rung-padding parity, the end-to-end generate flow, a
  # sharded model behind the engine across a bucket boundary. The seeded
  # zoolint fixture must flag undeclared zoo_shard_* / zoo_decode_*
  # names: a quiet drift check on the new families means the linter
  # regressed, not that the tree is clean.
  sharded)  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
              run -m "not slow" tests/test_generation.py \
              tests/test_sharded_serving.py
            echo "== zoolint: drift must flag undeclared shard/decode names"
            drift="$(python -m analytics_zoo_tpu.analysis --no-baseline \
                       tests/fixtures/zoolint 2>&1 || true)"
            for name in zoo_shard_hbm_bogus_bytes \
                        zoo_decode_steps_bogus_total \
                        ZOO_SERVING_DECODE_BOGUS_SEQ; do
              if ! grep -q "$name" <<<"$drift"; then
                echo "catalog drift missed the seeded $name violation" >&2
                exit 1
              fi
            done
            ;;
  # step-level continuous batching + paged KV + speculative decode
  # (ISSUE 16): scheduler parity/spec units, the sampling contract, the
  # kv-page-leak dataflow rule, the warmed decode grid, the self-draft
  # accept ratio at exactly 1.0, a generate flood under interactive
  # probes — the seeded allocator leaks must fire by file.
  decode)   run -m "not slow" tests/test_decode_scheduler.py \
                tests/test_generation.py tests/test_zoolint_dataflow.py
            echo "== zoolint: seeded kv page leaks must fire"
            drift="$(python -m analytics_zoo_tpu.analysis --no-baseline \
                       tests/fixtures/zoolint 2>&1 || true)"
            if [ "$(grep "kv-page-leak" <<<"$drift" | \
                    grep -c "serving/bad_kv_page_leak.py")" -ne 2 ]; then
              echo "zoolint missed a seeded kv page leak" >&2
              exit 1
            fi
            # the paged-table fixture holds exactly ONE leak (the guard
            # raise) — its clean twin must stay silent
            if [ "$(grep "kv-page-leak" <<<"$drift" | \
                    grep -c "serving/bad_paged_table_leak.py")" -ne 1 ]; then
              echo "zoolint missed the seeded paged-table leak" >&2
              exit 1
            fi
            echo "== zoolint: drift must flag undeclared paged/kv names"
            for name in zoo_paged_attn_bogus_total zoo_kv_quant_bogus_bytes \
                        ZOO_KV_BOGUS_DTYPE; do
              if ! grep -q "$name" <<<"$drift"; then
                echo "catalog drift missed the seeded $name violation" >&2
                exit 1
              fi
            done
            ;;
  # metric history + cost attribution (ISSUE 17): the windowed store's
  # quantile/rate algebra, exemplar->/trace links, fleet window merge,
  # the end-to-end cost drill (slow-marked, runs here), the lane-depth
  # ring scraped from /metrics/history mid-flood. The seeded zoolint
  # fixture must flag an undeclared zoo_ts_* name: a quiet drift check
  # on the new families means the linter regressed.
  observability) run tests/test_timeseries.py
            echo "== zoolint: drift must flag undeclared history names"
            drift="$(python -m analytics_zoo_tpu.analysis --no-baseline \
                       tests/fixtures/zoolint 2>&1 || true)"
            for name in zoo_ts_points_bogus ZOO_TS_BOGUS_TICK_S; do
              if ! grep -q "$name" <<<"$drift"; then
                echo "catalog drift missed the seeded $name violation" >&2
                exit 1
              fi
            done
            ;;
  release)  bash "$(dirname "$0")/release.sh" ;;
  all)      lint_zoolint
            run tests/ ;;
  *) echo "unknown lane: $lane" >&2; exit 2 ;;
esac
