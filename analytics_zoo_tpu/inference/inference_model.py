"""InferenceModel — thread-safe, high-concurrency model inference.

TPU-native analog of the reference's inference engine
(zoo/.../pipeline/inference/InferenceModel.scala:28-62 and
AbstractInferenceModel.java): where the reference keeps a
``LinkedBlockingQueue`` of ``concurrentNum`` deep-copied model instances so
multiple request threads can each take a private copy, here device weights
are immutable jax arrays shared by all callers, and the "copies" become one
**compiled-executable cache** keyed by input shape (an XLA executable is
reusable concurrently; recompiles only happen per new shape bucket). A
semaphore still bounds in-flight predicts at ``concurrent_num`` to provide
the same backpressure semantics as the reference's blocking queue.

Loader parity (ref InferenceModel.scala doLoadBigDL:96 / doLoadTensorflow:121
/ doLoadPyTorch:249 / doLoadOpenVINO:282 — all foreign-runtime loads):

- ``load_zoo(model)`` / ``load(path)``      — zoo keras/ZooModel (≈ doLoadBigDL)
- ``load_flax(module, sample_input, ...)``  — any flax.linen module
- ``load_torch(torch_module, sample_input)``— torch nn.Module converted to a
  jax forward (≈ doLoadPyTorch; see net/torch_net.py)
- ``load_checkpoint(path)``                 — weights from an Estimator
  checkpoint directory into the current model

Batching: predict pads the tail batch up to the bucket size and masks it
off, so every request shape hits one of a small set of executables (the
reference instead re-runs the graph at the raw batch,
TFNet.scala:179-265 — fine for CPU, recompile-per-shape on XLA).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.common import compile_ahead, resilience, telemetry


def _as_tuple(x):
    return tuple(x) if isinstance(x, (list, tuple)) else (x,)


def _warm_many_async(todo):
    """Daemon thread warming ``(cache, avals)`` pairs — warm_decode's
    grid may span the plain and the paged executable caches. Smallest
    first, same as ``ExecutableCache.warm_async``; a failed build does
    not kill the thread (``warm`` logs and counts it on the cache, and
    the shape compiles in-band later)."""
    def size(item):
        _, avals = item
        return int(np.prod(avals[1].shape)) if len(avals) > 1 else 0

    def work():
        for cache, avals in sorted(todo, key=size):
            cache.warm(*avals)

    t = threading.Thread(target=work, name="zoo-warm-decode", daemon=True)
    t.start()
    return t


class InferenceModel:
    """Thread-safe inference holder with a jitted-executable cache."""

    def __init__(self, concurrent_num: int = 1):
        # before the first compile: a loader's module.init is cached too
        compile_ahead.configure_persistent_cache()
        self.concurrent_num = int(concurrent_num)
        self._sem = threading.Semaphore(self.concurrent_num)
        self._lock = threading.Lock()
        self._apply = None          # (params, *inputs) -> outputs
        self._params = None
        self._jitted = None
        self._n_inputs = 1
        # set by quantize(mode="int8"): {dense path: calibrated |x|max}
        self._act_ranges = None
        # compile-ahead state: the batch-bucket ladder predict chunks
        # against, the per-sample input spec warmup builds avals from
        # (captured at load when a sample_input is given, else observed on
        # the first dispatch), the AOT executable cache dispatches run
        # through, and the live warmup threads wait_warm() joins
        self._ladder: Optional[compile_ahead.BucketLadder] = None
        self._sample_spec = None    # ((sample_shape, dtype), ...) per input
        self._exec_cache: Optional[compile_ahead.ExecutableCache] = None
        self._warm_threads: list = []
        # set by shard(): the mesh executable the dispatch seam rides —
        # params partitioned per strategy, avals carrying shardings
        self._sharded = None
        # paged decode seam (built lazily by paged_decode_step_fn): the
        # forward with ops/paged_attention.paged_gather fused under it
        self._paged_jitted = None
        self._paged_cache: Optional[compile_ahead.ExecutableCache] = None

    # ------------------------------------------------------------- loaders
    def load_zoo(self, model) -> "InferenceModel":
        """Load a zoo keras model (KerasNet) or ZooModel instance
        (ref doLoadBigDL, InferenceModel.scala:96)."""
        from analytics_zoo_tpu.keras.models import KerasNet

        import jax
        import jax.numpy as jnp

        net = model.model if hasattr(model, "model") and isinstance(
            getattr(model, "model"), KerasNet) else model
        est = net.estimator
        est._init_state()
        adapter = est.adapter
        # Deep-copy onto fresh device buffers: the estimator's train step
        # donates its state (donate_argnums=0), so aliasing est._state here
        # would leave this model pointing at invalidated TPU buffers after a
        # subsequent est.fit().
        state = jax.tree_util.tree_map(
            jnp.array,
            {"params": est._state["params"],
             "model_state": est._state["model_state"]})

        def apply_fn(state, *xs):
            out, _ = adapter.apply(state["params"], state["model_state"],
                                   xs if len(xs) > 1 else xs[0], False, None)
            return out

        self._install(apply_fn, state, adapter.n_inputs)
        return self

    def load(self, path: str) -> "InferenceModel":
        """Load a saved ZooModel directory (ref doLoadBigDL from file)."""
        from analytics_zoo_tpu.models.common import ZooModel
        return self.load_zoo(ZooModel.load_model(path))

    def load_flax(self, module, sample_input, params=None,
                  rng_seed: int = 0) -> "InferenceModel":
        """Load any flax.linen module; ``sample_input`` initialises params
        when none are given."""
        import jax

        args = _as_tuple(sample_input)
        if params is None:
            params = module.init(jax.random.PRNGKey(rng_seed), *args)

        def apply_fn(state, *xs):
            return module.apply(state["params"], *xs)

        self._install(apply_fn, {"params": params}, len(args))
        self._remember_spec(args, overwrite=True)
        return self

    def load_openvino(self, model_path: str, weight_path: str,
                      batch_size: int = 0) -> "InferenceModel":
        """Load an OpenVINO IR model (ref
        pyzoo/zoo/pipeline/inference/inference_model.py:69 load_openvino
        → native OpenVINO engine; here the IR is parsed and translated to
        a jitted jax function, net/openvino_net.py, so the same published
        artifacts serve on TPU). ``batch_size`` is accepted for API parity
        (batching is dynamic here)."""
        from analytics_zoo_tpu.net.openvino_net import OpenVINONet

        net = OpenVINONet(model_path, weight_path, jit=False)

        def apply_fn(state, *xs):
            return net.apply_fn({"params": state["params"]}, *xs)

        self._install(apply_fn, {"params": net.variables["params"]},
                      net.n_inputs)
        return self

    def load_torch(self, torch_module, sample_input) -> "InferenceModel":
        """Convert a torch nn.Module into a jax forward and load it
        (ref doLoadPyTorch, InferenceModel.scala:249 — there the module runs
        inside an embedded CPython; here it is *translated* so inference runs
        on the TPU)."""
        from analytics_zoo_tpu.net.torch_net import torch_to_jax

        apply_fn, variables = torch_to_jax(torch_module)
        n = len(_as_tuple(sample_input))

        def wrapped(state, *xs):
            return apply_fn({"params": state["params"],
                             "buffers": state["model_state"]}, *xs)

        self._install(wrapped, {"params": variables["params"],
                                "model_state": variables["buffers"]}, n)
        self._remember_spec(_as_tuple(sample_input), overwrite=True)
        return self

    def load_checkpoint(self, path: str) -> "InferenceModel":
        """Restore weights saved by ``Estimator.save``/checkpointing into
        the currently-loaded model (ref doLoadBigDL weight path)."""
        from analytics_zoo_tpu.learn import checkpoint as ckpt_lib
        import jax

        if self._params is None:
            raise RuntimeError("load a model before load_checkpoint")
        found = ckpt_lib.find_latest_checkpoint(path)
        target = path if found is None else found[0]
        host = jax.device_get(self._params)
        # Estimator checkpoints store {step, params, opt_state, model_state};
        # restore against a matching skeleton then keep only what we hold.
        skeleton = {"step": np.zeros((), np.int32),
                    "params": host.get("params"),
                    "opt_state": None,
                    "model_state": host.get("model_state", {})}
        try:
            state, _ = ckpt_lib.load_checkpoint(target, skeleton)
            new = {"params": state["params"]}
            if "model_state" in host:
                new["model_state"] = state.get("model_state",
                                               host["model_state"])
        except Exception:
            state, _ = ckpt_lib.load_checkpoint(target, host)
            new = state
        with self._lock:
            # executables key on shapes, not values — no re-jit needed
            self._params = new
        return self

    def quantize(self, min_elems: int = 1024, mode: str = "weight",
                 calibration_data=None) -> "InferenceModel":
        """Post-training int8 quantization (ref BigDL ``model.quantize()``
        int8 inference — SURVEY §6: "2× speedup, 4× model-size reduction").

        ``mode="weight"`` (default): matmul/conv kernels stored int8 with
        per-channel scales; dequantization runs inside the jitted forward
        so weights stay int8 in HBM (4× smaller).

        ``mode="int8"``: ALSO quantizes activations — a calibration pass
        over ``calibration_data`` (ndarray / tuple, or list of batches)
        records per-Dense input ranges (the reference's MKL int8
        calibration), then every calibrated ``nn.Dense`` executes as an
        int8×int8→int32 ``dot_general`` — the MXU's int8 path. Covers
        flax/zoo-keras models; composes with the weight storage
        quantization (applied first)."""
        from analytics_zoo_tpu.inference.quantize import (
            calibrate_activations, dequantize_tree, int8_apply,
            quantize_tree,
        )

        if mode not in ("weight", "int8"):
            raise ValueError(f"mode must be 'weight' or 'int8', got {mode!r}")
        with self._lock:
            if self._apply is None:
                raise RuntimeError("load a model before quantize")
            orig_apply = self._apply
            qstate = quantize_tree(self._params, min_elems=min_elems)

        def q_apply(state, *xs):
            return orig_apply(dequantize_tree(state), *xs)

        if mode == "int8":
            if calibration_data is None:
                raise ValueError(
                    "mode='int8' needs calibration_data (a batch or list "
                    "of batches) for the activation-range pass")
            batches = calibration_data \
                if isinstance(calibration_data, list) else [calibration_data]
            if not batches:
                raise ValueError(
                    "mode='int8': calibration_data is empty — pass at "
                    "least one batch to calibrate activation ranges")
            act_amax = calibrate_activations(q_apply, qstate, batches)
            # introspection: per-layer calibrated |x|max ranges
            self._act_ranges = act_amax
            self._install(int8_apply(q_apply, act_amax), qstate,
                          self._n_inputs)
            return self

        self._install(q_apply, qstate, self._n_inputs)
        return self

    def _install(self, apply_fn, params, n_inputs):
        with self._lock:
            self._apply = apply_fn
            self._params = params
            self._n_inputs = n_inputs
            # recompile accounting: every new shape bucket shows up in
            # zoo_jit_cache_misses_total{fn="inference_model"}
            self._jitted = telemetry.instrument_jit(
                apply_fn, name="inference_model")
            # warm dispatches bypass jit entirely through the AOT
            # executable cache; a re-install (load_*, quantize) drops the
            # old executables — the new forward needs new ones
            self._exec_cache = compile_ahead.ExecutableCache(
                self._jitted, name="inference_model")
            # a re-install also invalidates any mesh layout: the new
            # forward must be re-sharded explicitly
            self._sharded = None
            # and the paged decode seam: it closes over the old forward
            self._paged_jitted = None
            self._paged_cache = None

    def shard(self, strategy, param_rules=None, mesh=None,
              devices=None) -> "InferenceModel":
        """Repartition the loaded model onto a device mesh: parameters
        placed per the :class:`~analytics_zoo_tpu.parallel.strategy.
        ShardingStrategy` (e.g. ``"tp8"``, ``"fsdp"``, ``"dp2,tp4"``)
        and every subsequent predict/warm dispatch runs the mesh
        executable. The serving seam above (bucket ladder, assembly
        loop, warmup) is unchanged — executables key on batch
        shape/dtype, and warmup walks the ladder with sharded avals so
        bucket growth stays a stall-free swap."""
        from analytics_zoo_tpu.parallel.sharded_executable import (
            ShardedExecutable,
        )

        with self._lock:
            if self._apply is None:
                raise RuntimeError("load a model before shard")
            apply_fn, params = self._apply, self._params
        se = ShardedExecutable(apply_fn, params, strategy,
                               param_rules=param_rules, mesh=mesh,
                               devices=devices, name="inference_model")
        with self._lock:
            self._params = se.params
            self._jitted = se._jitted
            self._exec_cache = se.cache
            self._sharded = se
        return self

    def shard_info(self) -> Optional[Dict[str, Any]]:
        """Per-shard HBM accounting for the mesh executable (None when
        unsharded) — the `/healthz` payload proving no single device
        holds the full model."""
        with self._lock:
            se = self._sharded
        if se is None:
            return None
        hbm = se.shard_hbm_bytes()
        return {"strategy": str(se.strategy), "n_shards": se.n_shards,
                "total_param_bytes": se.total_param_bytes(),
                "shard_hbm_bytes": hbm}

    # ------------------------------------------------------ compile-ahead
    def _remember_spec(self, xs, overwrite: bool = False):
        """Record the per-sample (shape, dtype) of every input — what
        ``warm_up`` builds batched avals from. Loaders with a
        ``sample_input`` overwrite (authoritative); observed dispatch
        shapes only fill an empty spec."""
        try:
            spec = tuple((tuple(a.shape[1:]), np.dtype(a.dtype))
                         for a in xs)
        except Exception:
            return
        with self._lock:
            if overwrite or self._sample_spec is None:
                self._sample_spec = spec

    def has_warm_spec(self) -> bool:
        """True once the input spec needed for AOT warmup is known."""
        with self._lock:
            return self._sample_spec is not None

    def set_ladder(self, ladder, max_batch_size: Optional[int] = None
                   ) -> "InferenceModel":
        """Attach a batch-bucket ladder: ``predict`` pads each tail chunk
        to the nearest rung (instead of the full batch bucket) so tails
        reuse smaller pre-built executables. Pass a
        :class:`~analytics_zoo_tpu.common.compile_ahead.BucketLadder` or
        ``(min_batch_size, max_batch_size)`` ints."""
        if not isinstance(ladder, compile_ahead.BucketLadder):
            ladder = compile_ahead.BucketLadder(int(ladder), max_batch_size)
        with self._lock:
            self._ladder = ladder
        return self

    def _aot_avals(self, params, spec, rung):
        with self._lock:
            sharded = self._sharded
        # the avals carry each leaf's sharding: an AOT build lowered
        # without it compiles a different executable than the live
        # dispatch needs, so the "warm" rung would recompile on first use
        p_avals = compile_ahead.tree_avals(params)
        if sharded is not None:
            return (p_avals,) + sharded.batch_avals(spec, rung)
        return (p_avals,) + compile_ahead.batch_avals(spec, rung)

    def warm_up(self, rungs=None, sample_input=None, block: bool = False):
        """AOT-compile executables for the given batch ``rungs`` (default:
        the attached ladder's) on a background daemon thread — the serving
        engine calls this off the serve thread so bucket growth becomes a
        stall-free swap. ``sample_input`` records the input spec when the
        loader didn't capture one. ``block=True`` compiles synchronously.
        Returns the warmup thread (None when there is nothing to warm or
        no spec yet); ``wait_warm`` joins all outstanding ones."""
        if sample_input is not None:
            self._remember_spec(
                tuple(np.asarray(a) for a in _as_tuple(sample_input)),
                overwrite=True)
        with self._lock:
            spec, cache = self._sample_spec, self._exec_cache
            params, ladder = self._params, self._ladder
        if cache is None or spec is None:
            return None
        if rungs is None:
            rungs = ladder.rungs if ladder is not None else ()
        # ZOO_CPU_FALLBACK=1: each rung also gets a CPU executable so a
        # wedged backend fails over to already-compiled code (ISSUE 7)
        want_cpu = resilience.cpu_fallback_enabled()
        todo = []
        for rung in sorted({int(r) for r in rungs}):
            avals = self._aot_avals(params, spec, rung)
            if not cache.ready(*avals) or \
                    (want_cpu and not cache.cpu_ready(*avals)):
                todo.append(avals)
        if not todo:
            return None
        if block:
            for avals in todo:
                cache.warm(*avals)
                if want_cpu:
                    cache.warm_cpu(*avals)
            return None
        t = cache.warm_async(todo, cpu_also=want_cpu)
        with self._lock:
            self._warm_threads = [w for w in self._warm_threads
                                  if w.is_alive()] + [t]
        return t

    def wait_warm(self, timeout: Optional[float] = None
                  ) -> "InferenceModel":
        """Join every outstanding warmup thread (best effort under
        ``timeout`` seconds total)."""
        with self._lock:
            threads = list(self._warm_threads)
        deadline = None if timeout is None else \
            time.monotonic() + float(timeout)
        for t in threads:
            t.join(None if deadline is None
                   else max(0.0, deadline - time.monotonic()))
        return self

    def rung_ready(self, rung: int) -> bool:
        """True when an AOT executable exists for batch size ``rung`` —
        the serving engine's gate for stall-free bucket growth. Unknown
        spec reads as not-ready (growing would compile in-band)."""
        with self._lock:
            spec, cache, params = \
                self._sample_spec, self._exec_cache, self._params
        if cache is None or spec is None:
            return False
        try:
            return cache.ready(*self._aot_avals(params, spec, rung))
        except Exception:
            return False

    # ------------------------------------------------------------ generate
    def warm_decode(self, max_seq_len: int, rungs=None, seq_rungs=None,
                    block: bool = False, verify_k: int = 0,
                    paged_pool=None):
        """AOT-compile the decode grid: every (batch rung × seq-length
        rung) shape a ``generate`` up to ``max_seq_len`` can present, so
        the decode loop never recompiles — the KV cache's rung growth is
        a swap onto an already-built executable. Needs a 2-input
        (encoder, decoder) spec; the decoder's time axis is rewritten per
        seq rung. ``verify_k > 0`` extends the grid top so the
        speculative k-wide verify step (live length + k drafts + bonus)
        lands on a warmed rung too; chunked prefill needs no extra shapes
        — prefill positions fill the same rung buffers the decode steps
        run. ``paged_pool=(n_pages, page_size)`` additionally warms the
        PAGED step executables on the same grid (pool dtype from
        ``ZOO_KV_DTYPE``), so the scheduler's first paged dispatch hits a
        built shape. Returns the warmup thread (None when nothing to
        do)."""
        from analytics_zoo_tpu.inference import generation

        with self._lock:
            spec, cache = self._sample_spec, self._exec_cache
            params, ladder = self._params, self._ladder
        if cache is None or spec is None or len(spec) < 2:
            return None
        if seq_rungs is None:
            seq_rungs = generation.seq_ladder(
                int(max_seq_len) + max(0, int(verify_k))).rungs
        if rungs is None:
            rungs = ladder.rungs if ladder is not None else ()
        todo = [(cache, avals)
                for avals in compile_ahead.decode_grid_specs(
                    spec, rungs, seq_rungs,
                    lambda dspec, rung: self._aot_avals(
                        params, dspec, rung))
                if not cache.ready(*avals)]
        if paged_pool is not None:
            for pcache, avals in self._paged_decode_avals(
                    paged_pool, spec, params, rungs, seq_rungs):
                todo.append((pcache, avals))
        if not todo:
            return None
        if block:
            for c, avals in todo:
                c.warm(*avals)
            return None
        t = _warm_many_async(todo)
        with self._lock:
            self._warm_threads = [w for w in self._warm_threads
                                  if w.is_alive()] + [t]
        return t

    def _paged_decode_avals(self, paged_pool, spec, params, rungs,
                            seq_rungs):
        """Yield (cache, avals) for every unbuilt PAGED step executable
        on the (batch rung × seq rung) grid. The paged seam materializes
        the decoder at ``width * page_size`` positions, so distinct seq
        rungs sharing a page width share one executable."""
        import jax
        from analytics_zoo_tpu.inference import quantize

        n_pages, page_size = (int(v) for v in paged_pool)
        self._ensure_paged()
        with self._lock:
            pcache = self._paged_cache
        if pcache is None:
            return
        kv_dtype = quantize.resolve_kv_dtype(None)
        dim = int(spec[-1][0][-1])
        pool_aval = jax.ShapeDtypeStruct((n_pages, page_size, dim),
                                         kv_dtype)
        scales_aval = jax.ShapeDtypeStruct((n_pages,), np.float32)
        seen = set()
        for rung in sorted({int(r) for r in rungs}):
            for sr in sorted({int(s) for s in seq_rungs}):
                width = -(-sr // page_size)
                if (rung, width) in seen:
                    continue
                seen.add((rung, width))
                avals = self._aot_avals(params, spec[:1], rung) + (
                    pool_aval, scales_aval,
                    jax.ShapeDtypeStruct((rung, width), np.int32),
                    jax.ShapeDtypeStruct((rung,), np.int32))
                if not pcache.ready(*avals):
                    yield pcache, avals

    def decode_step_fn(self):
        """The scheduler-facing step seam: one wide ``(enc, dec) -> out``
        dispatch through the AOT executables (async submit + traced
        fetch). A :class:`~analytics_zoo_tpu.inference.decode_scheduler.
        DecodeScheduler` built on this callable runs every step on the
        same (batch rung × seq rung) grid ``warm_decode`` compiled."""
        with self._lock:
            if self._apply is None:
                raise RuntimeError("load a model before decode_step_fn")
            if self._n_inputs != 2:
                raise ValueError(
                    "decode needs a 2-input (encoder, decoder) model, "
                    f"got {self._n_inputs} inputs")

        def step(enc, dec):
            return np.asarray(self.predict_fetch(
                self.predict_async((enc, dec))))

        return step

    def _ensure_paged(self):
        """Build the paged decode dispatch seam once per installed
        forward: ``paged_apply(state, enc, pool, scales, table, lengths)``
        runs ``ops/paged_attention.paged_gather`` INSIDE the jitted step
        — the per-page host copy of ``gather_into`` becomes an on-device
        gather driven by the scalar-prefetched page table — then feeds
        the gathered buffer to the original forward. Because that buffer
        is bitwise the host-gathered one, outputs match the plain seam
        bit for bit."""
        with self._lock:
            if self._paged_cache is not None:
                return
            orig_apply = self._apply

        def paged_apply(state, enc, pool, scales, table, lengths):
            from analytics_zoo_tpu.ops import paged_attention
            # pinned dispatch, decision by verdict lookup only: this
            # traces under whoever owns the jit (serve loop / warmup
            # thread, possibly holding the model lock), so the path must
            # never reach a tuner measurement
            dec = paged_attention.paged_gather_pinned(
                pool, table, lengths, scales=scales,
                use_kernel=paged_attention.gather_decision(pool, table))
            return orig_apply(state, enc, dec)

        jitted = telemetry.instrument_jit(
            paged_apply, name="inference_model_paged")
        cache = compile_ahead.ExecutableCache(
            jitted, name="inference_model_paged")
        with self._lock:
            if self._paged_cache is None and self._apply is orig_apply:
                self._paged_jitted = jitted
                self._paged_cache = cache

    def paged_decode_step_fn(self):
        """Paged counterpart of :meth:`decode_step_fn`: one wide
        ``(enc, pool, scales, table, lengths) -> out`` dispatch where the
        per-sequence page gather runs inside the jitted forward. The
        decoder buffer materializes at ``table_width * page_size``
        positions — the seq rung rounded up to a page multiple — which is
        output-invisible for live positions (the causal rung-padding
        parity generation.py pins). int8 pools ship with their per-page
        scales; float pools pass all-ones (``x * 1.0`` is bitwise
        ``x``)."""
        with self._lock:
            if self._apply is None:
                raise RuntimeError(
                    "load a model before paged_decode_step_fn")
            if self._n_inputs != 2:
                raise ValueError(
                    "decode needs a 2-input (encoder, decoder) model, "
                    f"got {self._n_inputs} inputs")
        self._ensure_paged()

        def step(enc, pool, scales, table, lengths):
            self._ensure_paged()   # rebuilt lazily after a re-install
            with self._lock:
                params, cache = self._params, self._paged_cache
            pending = cache(params, np.asarray(enc),
                            np.ascontiguousarray(pool),
                            np.asarray(scales, np.float32),
                            np.asarray(table, np.int32),
                            np.asarray(lengths, np.int32))
            return np.asarray(telemetry.traced_device_get(pending))

        return step

    def generate(self, input_seq, start_sign, max_new_tokens: int = 16, *,
                 mode: str = "greedy", temperature: float = 1.0,
                 seed: Optional[int] = None, ladder=None,
                 trace_ids: Sequence[str] = (), draft=None,
                 spec_k: int = 4) -> np.ndarray:
        """Autoregressive generation through the AOT dispatch seam:
        sharded prefill + decode over the bucketed KV rungs, every step
        running the (batch rung × seq rung) executables ``warm_decode``
        built — never a per-request recompile. The loaded model must be a
        2-input encoder/decoder (e.g. the seq2seq zoo via ``load_zoo``).

        ``draft`` (another InferenceModel, or a bare ``(enc, dec)``
        callable) switches to speculative decoding through the step
        scheduler: the draft proposes ``spec_k`` tokens per step and this
        model verifies them in one wide step — greedy output stays
        bitwise identical to plain decode; without ``draft`` the classic
        step-by-step loop runs unchanged. Each row keeps a private rng
        stream under ``draft`` (seeded ``seed + row``), whereas the plain
        loop draws one batch-wide stream. Returns the generated
        ``[batch, max_new_tokens, output_dim]`` sequence."""
        from analytics_zoo_tpu.inference import generation

        with self._lock:
            if self._apply is None:
                raise RuntimeError("load a model before generate")
            if self._n_inputs != 2:
                raise ValueError(
                    "generate needs a 2-input (encoder, decoder) model, "
                    f"got {self._n_inputs} inputs")
        if draft is not None:
            from analytics_zoo_tpu.inference import decode_scheduler

            draft_fn = (draft.decode_step_fn()
                        if hasattr(draft, "decode_step_fn") else draft)
            input_seq = np.asarray(input_seq)
            start = np.asarray(start_sign, np.float32)
            sched = decode_scheduler.DecodeScheduler(
                self.decode_step_fn(),
                max_batch=max(1, int(input_seq.shape[0])),
                max_seq=int(max_new_tokens) + 1,
                draft_fn=draft_fn, spec_k=spec_k)
            seqs = [sched.admit(
                        input_seq[i], start[i], max_new_tokens,
                        mode=mode, temperature=temperature,
                        seed=None if seed is None else int(seed) + i,
                        tag=i,
                        trace_uri=(trace_ids[i]
                                   if i < len(trace_ids) else None))
                    for i in range(input_seq.shape[0])]
            sched.drain()
            return np.stack([s.result for s in seqs])
        if ladder is None:
            ladder = generation.seq_ladder(int(max_new_tokens) + 1)
        return generation.decode_loop(
            self.decode_step_fn(), input_seq, start_sign,
            max_new_tokens, ladder=ladder, mode=mode,
            temperature=temperature, seed=seed, trace_ids=trace_ids)

    # ------------------------------------------------------------- predict
    def _snapshot(self):
        with self._lock:
            # one consistent snapshot: a concurrent load_* or
            # load_checkpoint can't mix model versions across chunks
            if self._apply is None:
                raise RuntimeError("no model loaded")
            return (self._params, self._jitted, self._n_inputs,
                    self._exec_cache, self._ladder)

    @staticmethod
    def _coerce(x, n_inputs) -> Tuple[np.ndarray, ...]:
        xs = _as_tuple(x)
        if len(xs) != n_inputs:
            if n_inputs == 1:
                xs = (np.asarray(x),)
            else:
                raise ValueError(
                    f"model takes {n_inputs} inputs, got {len(xs)}")
        return tuple(np.asarray(a) for a in xs)

    def _chunks(self, x, n_inputs, batch_size, ladder=None):
        """Split one logical batch into compile-bucket chunks, padding the
        tail so every shape hits an already-built executable: yields
        ``(chunk_tuple, n_valid)``. With a bucket ladder attached, the
        tail pads to its **nearest rung** instead of the full bucket —
        less pad waste, and the rung's executable is already warm."""
        xs = self._coerce(x, n_inputs)
        self._remember_spec(xs)
        n = xs[0].shape[0]
        if n == 0:
            raise ValueError("predict called on an empty batch")
        bs = int(batch_size) if batch_size else \
            (ladder.rung_for(n) if ladder is not None else n)
        for lo in range(0, n, bs):
            hi = min(lo + bs, n)
            chunk = tuple(a[lo:hi] for a in xs)
            valid = hi - lo
            rung = bs if ladder is None else \
                min(bs, ladder.rung_for(valid))
            yield compile_ahead.pad_to_rung(chunk, rung,
                                            site="inference"), valid

    def predict(self, x, batch_size: Optional[int] = None,
                pipeline_window: int = 2) -> np.ndarray:
        """Batch predict. ``x``: ndarray, tuple of ndarrays (multi-input),
        or an iterator/generator of such batches — a stream is consumed
        incrementally, one window's worth at a time, instead of being
        materialized up front.

        Chunks flow through a bounded in-flight dispatch window
        (``pipeline_window`` batches deep, common/pipeline_io.py): chunk
        N+1 is sliced/padded and dispatched while chunk N computes, and
        results are fetched only as the window retires them — never inline
        with a dispatch. ``pipeline_window=1`` reproduces the synchronous
        cadence. Outputs are bit-identical either way (same executables,
        same inputs; only the fetch schedule changes).

        Thread-safe; at most ``concurrent_num`` predicts run concurrently
        (ref InferenceModel.doPredict + model-queue take/offer)."""
        import jax
        from analytics_zoo_tpu.common.pipeline_io import DevicePipeline

        params, jitted, n_inputs, cache, ladder = self._snapshot()
        # warm rungs dispatch straight through the AOT executable cache —
        # the jit call path (and its recompile counter) is only the
        # fallback for shapes the cache cannot handle
        run = cache if cache is not None else \
            (lambda p, *c: jitted(p, *c))

        def chunks():
            if hasattr(x, "__next__"):       # stream of batches
                for b in x:
                    yield from self._chunks(b, n_inputs, batch_size,
                                            ladder)
            else:
                yield from self._chunks(x, n_inputs, batch_size, ladder)

        outs = []

        def take(comp):
            if comp.error is not None:
                raise comp.error
            outs.append(jax.tree_util.tree_map(
                lambda a: a[:comp.ctx], comp.result))

        with self._sem:
            pipe = DevicePipeline(lambda c: run(params, *c),
                                  window=max(1, int(pipeline_window)),
                                  trace_id="inference_predict")
            with pipe:
                for chunk, valid in chunks():
                    for comp in pipe.submit(chunk, ctx=valid):
                        take(comp)
                for comp in pipe.drain():
                    take(comp)
        if not outs:
            raise ValueError("predict called on an empty batch")
        leaves = [jax.tree_util.tree_leaves(o) for o in outs]
        treedef = jax.tree_util.tree_structure(outs[0])
        return jax.tree_util.tree_unflatten(
            treedef,
            [np.concatenate([l[i] for l in leaves])
             for i in range(len(leaves[0]))])

    def predict_async(self, x):
        """Dispatch ONE already-batched input (ndarray or multi-input
        tuple) without blocking — the serving engine's staged-dispatch
        hook. Returns an opaque pending value; pass it to
        ``predict_fetch`` for the host result. The caller owns batching
        and padding (the engine pads to its own bucket) and bounds
        in-flight work through its DevicePipeline window, so the
        ``concurrent_num`` semaphore is not taken here."""
        params, jitted, n_inputs, cache, _ = self._snapshot()
        xs = self._coerce(x, n_inputs)
        self._remember_spec(xs)
        if cache is not None:
            return cache(params, *xs)
        return jitted(params, *xs)

    def predict_fetch(self, pending):
        """Blocking host side of ``predict_async``."""
        return telemetry.traced_device_get(pending)

    def predict_cpu(self, x):
        """Synchronously predict ONE already-batched input on the host
        CPU device — the serving engine's failover dispatch while the
        accelerator backend is wedged. Goes through the executable
        cache's CPU rung (pre-built during warmup under
        ``ZOO_CPU_FALLBACK=1``) and deliberately bypasses the accelerator
        dispatch path — and its fault-injection seam — entirely."""
        import jax

        params, jitted, n_inputs, cache, _ = self._snapshot()
        xs = self._coerce(x, n_inputs)
        self._remember_spec(xs)
        if cache is not None:
            return jax.device_get(cache.cpu_call(params, *xs))
        with jax.default_device(jax.devices("cpu")[0]):
            return jax.device_get(jitted(params, *xs))

    def predict_classes(self, x, batch_size: Optional[int] = None,
                        zero_based_label: bool = True) -> np.ndarray:
        probs = np.asarray(self.predict(x, batch_size))
        classes = np.argmax(probs, axis=-1)
        return classes if zero_based_label else classes + 1

    # java-flavoured aliases (ref AbstractInferenceModel.java)
    do_predict = predict
    do_load = load
