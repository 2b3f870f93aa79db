#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, on
one TPU process, at the full width of BERT-base (12 blocks, hidden 768,
12 heads, vocab 30522, bf16 compute, batch 32, seq 128 — the shape of
the cell ``bert-base-train-b32-s128``) with random weights made from a seed:

- trainer: ``init_orca_context`` → ``Estimator.from_flax`` → ``fit`` on the
  default path and on the ``steps_per_loop`` scan path, then ``NeuralCF``
  at ml-1m scale through ``ncf.fit``;
- server: ``InferenceModel.load_flax`` → native broker built from source →
  ``ClusterServing`` → ``InputQueue``/``OutputQueue``, every record checked
  against a direct jitted forward; one ``generate`` record through the
  engine's ``DecodeScheduler``;
- kernels: every ``pl.pallas_call`` in ``ops/`` pinned on, compiled (never
  interpreted), against its pure-jax reference, then one sync ``tune_*``
  per family;
- four chips (when the host has them): tp/fsdp layouts that must put a
  share on every device.

Any failed check raises: there is no ``try/except`` that turns a phase
into a note, so no failure can end in exit 0. Without a TPU it refuses
(exit 2) before touching anything. The last line of stdout is one JSON
object naming the device as JAX reports it. Wall and set-up seconds are
printed for information only — they are not metrics.

    python chip_smoke.py            # on the chip, from the checkout root
"""

import gc
import glob
import json
import os
import sys
import time

#: switches that would let something other than the compiled chip path
#: pass for it (interpreter, CPU failover, injected faults)
REFUSED_ENV = ("ZOO_PALLAS_INTERPRET", "ZOO_CPU_FALLBACK", "ZOO_FAULT_PLAN")

BERT_BATCH, BERT_SEQ = 32, 128
BERT_STEPS = 8                  # optimizer steps per epoch, both paths
BERT_SCAN = 4                   # steps fused per dispatch on the scan path
NCF_USERS, NCF_ITEMS, NCF_CLASSES = 6040, 3706, 5       # ml-1m
NCF_BATCH, NCF_STEPS = 8000, 8
SERVE_MIN_RUNG, SERVE_MAX_RUNG = 8, 32
# enough backlog that dequeues at the bottom rung come back full well past
# the engine's grow-after streak: the burst is served on at least two rungs
SERVE_RECORDS = 24 * SERVE_MIN_RUNG
DECODE_HIDDEN, DECODE_TOKENS, DECODE_RUNG = 64, 8, 4
#: outputs computed in bf16: 8 ulps at the reference's top magnitude
BF16_RTOL = 2.0 ** -5

_setup = {"secs": 0.0, "cache_hits": 0, "cache_misses": 0}


def refuse(reason: str):
    print(f"chip_smoke: refused: {reason}", file=sys.stderr)
    sys.exit(2)


def check(ok, why: str):
    """The smoke's assertion: raises where ``assert`` would vanish under
    ``python -O``."""
    if not ok:
        raise AssertionError(why)


def preconditions() -> dict:
    """Refuse unless this process holds a TPU the peaks table knows and no
    switch is set that would hide it. Returns the device description."""
    for var in REFUSED_ENV:
        if os.environ.get(var):
            refuse(f"{var} is set; the smoke runs the compiled chip path "
                   f"only — unset it")
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        refuse(f"needs a TPU, but jax.devices()[0].platform is "
               f"{dev.platform!r} ({dev.device_kind}); JAX_PLATFORMS="
               f"{os.environ.get('JAX_PLATFORMS')!r}")
    from analytics_zoo_tpu.common import profiling
    if dev.device_kind not in profiling.PEAK_FLOPS:
        refuse(f"device_kind {dev.device_kind!r} is not in "
               f"profiling.PEAK_FLOPS {sorted(profiling.PEAK_FLOPS)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _listen_for_setup_time():
    """Sum JAX's own trace/lower/compile durations and count persistent
    cache hits and misses, so each phase can say how much of its wall was
    set-up."""
    import jax.monitoring

    def on_duration(event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            _setup["secs"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _setup["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _setup["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def _cache_entries(cache_dir) -> int:
    if not cache_dir:           # no persistent cache configured
        return 0
    return len(glob.glob(os.path.join(cache_dir, "*-cache")))


class Phase:
    """Times one phase; prints its wall, set-up seconds and compile-cache
    traffic (information only). A raise inside the block propagates."""

    def __init__(self, name: str, cache_dir: str, report: dict):
        self.name, self.cache_dir, self.report = name, cache_dir, report

    def __enter__(self):
        print(f"--- {self.name}", flush=True)
        self.t0 = time.perf_counter()
        self.before = dict(_setup)
        self.entries0 = _cache_entries(self.cache_dir)
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            print(f"--- {self.name}: FAILED after "
                  f"{time.perf_counter() - self.t0:.1f}s", flush=True)
            return
        info = {
            "wall_s": round(time.perf_counter() - self.t0, 1),
            "setup_s": round(_setup["secs"] - self.before["secs"], 1),
            "cache_hits":
                _setup["cache_hits"] - self.before["cache_hits"],
            "cache_misses":
                _setup["cache_misses"] - self.before["cache_misses"],
            "cache_entries_written":
                _cache_entries(self.cache_dir) - self.entries0,
        }
        self.report[self.name] = info
        print(f"--- {self.name}: ok {json.dumps(info)}", flush=True)


def _bert_classifier():
    """BERT-base encoder + 2-way head (one input: token ids)."""
    import flax.linen as nn
    import jax.numpy as jnp
    from analytics_zoo_tpu.text.bert import BertConfig, BertModule

    cfg = BertConfig(dtype=jnp.bfloat16)

    class Classifier(nn.Module):
        @nn.compact
        def __call__(self, ids, train: bool = False):
            _, pooled = BertModule(cfg, name="bert")(ids, train=train)
            return nn.Dense(2)(pooled)

    return Classifier(), cfg


def _bert_batches(rng, cfg):
    """One epoch of BERT_STEPS batches: random token ids, two classes."""
    import numpy as np
    n = BERT_BATCH * BERT_STEPS
    return (rng.integers(0, cfg.vocab, (n, BERT_SEQ)).astype(np.int32),
            rng.integers(0, 2, n).astype(np.int32))


def _ncf_batches(rng):
    """One epoch of NCF_STEPS batches of 1-based (user, item) ids."""
    import numpy as np
    u = rng.integers(1, NCF_USERS + 1, NCF_BATCH * NCF_STEPS)
    i = rng.integers(1, NCF_ITEMS + 1, NCF_BATCH * NCF_STEPS)
    return (np.stack([u, i], 1).astype(np.float32),
            ((u + i) % NCF_CLASSES).astype(np.int32))


def _assert_on_tpu(tree, what: str):
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    check(leaves, f"{what}: no parameters")
    for leaf in leaves:
        platforms = {d.platform for d in leaf.devices()}
        check(platforms == {"tpu"}, f"{what}: a leaf lives on {platforms}")


def _assert_losses(hist: dict, what: str):
    import numpy as np
    losses = [float(v) for v in hist["loss"]]
    check(len(losses) >= 2 and np.all(np.isfinite(losses)),
          f"{what}: losses {losses}")
    check(losses[0] != losses[-1], f"{what}: loss did not change {losses}")
    return [round(v, 4) for v in losses]


def _max_err(got, want):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"shapes {got.shape} != {want.shape}")
    check(np.all(np.isfinite(got)) and np.all(np.isfinite(want)),
          "non-finite values")
    return float(np.max(np.abs(got - want))), float(np.max(np.abs(want)))


def _counter(name: str, label: str) -> float:
    from analytics_zoo_tpu.common import telemetry
    fam = telemetry.snapshot().get(name, {})
    if not isinstance(fam, dict):
        return float(fam or 0.0)
    return float(fam.get(label, 0.0))


# ------------------------------------------------------------------ trainer

def phase_trainer():
    import jax
    import numpy as np
    from analytics_zoo_tpu.common import profiling, telemetry
    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.models.recommendation import NeuralCF

    module, cfg = _bert_classifier()
    rng = np.random.default_rng(0)
    x, y = _bert_batches(rng, cfg)
    est = Estimator.from_flax(
        model=module, loss="sparse_categorical_crossentropy_logits",
        optimizer="adam", sample_input=x[:2])
    # two epochs of BERT_STEPS optimizer steps on each path
    step_losses = _assert_losses(
        est.fit((x, y), epochs=2, batch_size=BERT_BATCH), "bert fit")
    scan_losses = _assert_losses(
        est.fit((x, y), epochs=2, batch_size=BERT_BATCH,
                steps_per_loop=BERT_SCAN), "bert fit steps_per_loop")
    _assert_on_tpu(est._state["params"], "bert params")
    used, source = profiling.hbm_bytes()
    check(source == "memory_stats", f"hbm_bytes source is {source!r}")
    n_params = sum(int(np.prod(p.shape)) for p in
                   jax.tree_util.tree_leaves(est._state["params"]))
    # fp32 params plus adam's two moments are resident at the least
    check(used >= 3 * 4 * n_params // len(jax.devices()),
          f"{used} bytes in use for {n_params} parameters")
    mfu = telemetry.snapshot().get("zoo_mfu")
    check(isinstance(mfu, float) and mfu > 0.0, f"zoo_mfu is {mfu!r}")
    print(f"bert-base: {n_params / 1e6:.1f}M params, losses step path "
          f"{step_losses}, scan path {scan_losses}; hbm {used / 2**30:.2f} "
          f"GiB ({source}); zoo_mfu published", flush=True)

    ncf = NeuralCF(user_count=NCF_USERS, item_count=NCF_ITEMS,
                   class_num=NCF_CLASSES)
    ncf.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                metrics=["accuracy"])
    ncf_losses = _assert_losses(
        ncf.fit(*_ncf_batches(rng), batch_size=NCF_BATCH, nb_epoch=2),
        "ncf fit")
    _assert_on_tpu(ncf.model.estimator._state["params"], "ncf params")
    print(f"ncf ml-1m: losses {ncf_losses}", flush=True)


# ------------------------------------------------------------------- server

def phase_server():
    import jax
    import numpy as np
    from analytics_zoo_tpu.inference import InferenceModel
    from analytics_zoo_tpu.models import Seq2Seq
    from analytics_zoo_tpu.serving import (
        Broker, ClusterServing, InputQueue, OutputQueue,
    )
    from analytics_zoo_tpu.serving.broker import build_native_broker

    module, cfg = _bert_classifier()
    rng = np.random.default_rng(1)
    ids = rng.integers(0, cfg.vocab,
                       (SERVE_RECORDS, BERT_SEQ)).astype(np.int32)
    params = module.init(jax.random.PRNGKey(0), ids[:1])
    im = InferenceModel().load_flax(module, ids[:1], params=params)
    # the reference: the same weights through a plain jitted forward
    forward = jax.jit(lambda p, a: module.apply(p, a))
    want = np.concatenate([
        np.asarray(forward(params, ids[k:k + SERVE_MAX_RUNG]))
        for k in range(0, SERVE_RECORDS, SERVE_MAX_RUNG)])

    # from source, into the git-ignored build dir: a binary copied along
    # with the tree must not stand in for one this machine can build
    check(build_native_broker(force=True), "native broker did not build")
    with Broker.launch(backend="native") as broker:
        check(broker.backend == "native", "the broker is not the native one")
        in_q = InputQueue(port=broker.port)
        out_q = OutputQueue(port=broker.port)
        eng = ClusterServing(im, broker.port, batch_size=SERVE_MIN_RUNG,
                             min_batch_size=SERVE_MIN_RUNG,
                             max_batch_size=SERVE_MAX_RUNG)
        start_rung = eng.batch_size
        with eng.start():
            eng.wait_warm(timeout=900.0)
            for rung in eng.ladder.rungs:
                check(im.rung_ready(rung), f"rung {rung} is not warm")
            misses = _counter("zoo_jit_cache_misses_total",
                              "fn=inference_model")
            uris = in_q.enqueue_batch(
                (f"bert{k}", {"x": ids[k]}) for k in range(SERVE_RECORDS))
            res = out_q.query_many(uris, timeout=300.0)
            peak_rung = eng.batch_size
        missing = [u for u, v in res.items() if v is None]
        check(not missing, f"{len(missing)} records unanswered")
        err, top = _max_err(np.stack([res[u] for u in uris]), want)
        tol = BF16_RTOL * max(1.0, top)
        check(err <= tol,
              f"served logits differ from the direct forward by {err}")
        check(peak_rung > start_rung,
              f"bucket stayed at rung {start_rung}: one rung served it all")
        recompiles = _counter("zoo_jit_cache_misses_total",
                              "fn=inference_model") - misses
        check(recompiles == 0, f"{recompiles} recompiles after wait_warm")
        check(im._exec_cache.fallbacks == 0,
              f"{im._exec_cache.fallbacks} dispatches left the AOT path")
        print(f"bert-base serving: {SERVE_RECORDS} records answered on "
              f"rungs {start_rung}..{peak_rung}, max |err| {err:.2e} "
              f"(tol {tol:.2e}), 0 recompiles, 0 fallbacks", flush=True)

        # one generate record through the engine's step scheduler
        s2s = Seq2Seq(input_dim=8, output_dim=8, hidden_size=DECODE_HIDDEN,
                      rnn_type="gru", encoder_seq_len=8, decoder_seq_len=4)
        gim = InferenceModel().load_zoo(s2s)
        enc = rng.standard_normal((8, 8)).astype(np.float32)
        start = np.zeros(8, np.float32)
        geng = ClusterServing(gim, broker.port, batch_size=DECODE_RUNG,
                              max_batch_size=DECODE_RUNG, block_ms=10,
                              warmup=False)
        with geng.start():
            uri = in_q.enqueue("gen0",
                               generate={"max_new_tokens": DECODE_TOKENS},
                               x=enc, start=start)
            out = out_q.query(uri, timeout=300.0)
            steps_run = geng.decode_state()["steps_run"]
        check(out is not None, "generate record unanswered")
        out = np.asarray(out)
        # the engine pads its batch to the rung by repeating the last row;
        # the reference decodes the same rung directly, so both run the
        # same executables (greedy feedback amplifies any other difference)
        direct = np.asarray(gim.generate(
            np.repeat(enc[None], DECODE_RUNG, axis=0),
            np.repeat(start[None], DECODE_RUNG, axis=0),
            DECODE_TOKENS, mode="greedy"))[0]
        check(out.shape == (DECODE_TOKENS, 8) and np.all(np.isfinite(out)),
              f"generate result {out.shape} is misshapen or not finite")
        check(np.array_equal(out, direct),
              f"generate through the engine differs from "
              f"InferenceModel.generate:\n{out}\n{direct}")
        check(steps_run >= DECODE_TOKENS,
              f"the step scheduler ran {steps_run} steps")
        check(gim._exec_cache.fallbacks == 0,
              f"{gim._exec_cache.fallbacks} decode dispatches left the AOT "
              f"path")
        print(f"generate: {DECODE_TOKENS} tokens in {steps_run} scheduler "
              f"steps, equal to InferenceModel.generate", flush=True)


# ------------------------------------------------------------------ kernels

def _check(table: list, name: str, got, want, rtol: float):
    """One kernel-vs-reference row; ``rtol`` is relative to the
    reference's largest magnitude (0 demands exact equality)."""
    import jax
    errs = [_max_err(g, w) for g, w in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want))]
    err = max(e for e, _ in errs)
    for e, top in errs:
        check(e <= rtol * max(top, 1e-6),
              f"{name}: max |err| {e} against a reference of magnitude {top}")
    table.append({"kernel": name, "max_err": err})
    print(f"  {name}: compiled, max |err| {err:.3g}", flush=True)


def _flash_checks(table, b, s, h, d, causal, kv_heads=None):
    """``kv_heads``: k and v at fewer heads than q, read by their groups;
    the reference takes them repeated."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import flash_attention as fa

    q, k, v = (jax.random.normal(key, (b, s, heads, d), jnp.bfloat16)
               for key, heads in zip(
                   jax.random.split(jax.random.PRNGKey(0), 3),
                   (h, kv_heads or h, kv_heads or h)))
    tag = (f"b{b}s{s}h{h}{f'/{kv_heads}' if kv_heads else ''}d{d}"
           f"{' causal' if causal else ''}")

    def sq(a):
        return (a.astype(jnp.float32) ** 2).sum()

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal)

    def reference(q, k, v):
        return fa.blockwise_attention(q, *fa.repeat_kv_heads(q, k, v),
                                      causal=causal)

    def kernel_lse(q, k, v):
        out, lse = fa.flash_attention_with_lse(q, k, v, causal)
        return sq(out) + (0.1 * lse).sum()

    def reference_lse(q, k, v):
        out, lse = fa.blockwise_attention(
            q, *fa.repeat_kv_heads(q, k, v), causal=causal, return_lse=True)
        return sq(out) + (0.1 * lse).sum()

    def both(f, g):
        return jax.jit(f)(q, k, v), jax.jit(g)(q, k, v)

    def grad(f):
        return jax.grad(f, argnums=(0, 1, 2))

    _check(table, f"flash fwd {tag}", *both(kernel, reference), BF16_RTOL)
    _check(table, f"flash grad {tag}", *both(
        grad(lambda *a: sq(kernel(*a))),
        grad(lambda *a: sq(reference(*a)))), BF16_RTOL)
    _check(table, f"flash with_lse grad {tag}", *both(
        grad(kernel_lse), grad(reference_lse)), BF16_RTOL)


def _norm_rotary_checks(table, b, s, heads, d=128):
    """The q/k norm and rotary positions as kernels against the XLA chain
    they replace, in float32 on the same bfloat16 rows, at positions that
    repeat as block diffusion's do: value and gradient."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from analytics_zoo_tpu.ops import attention, norm_rotary

    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(keys[0], (b, s, heads * d), jnp.bfloat16)
    scale = 1 + 0.1 * jax.random.normal(keys[1], (d,))
    cotangent = jax.random.normal(keys[2], x.shape, jnp.bfloat16)
    positions = np.tile(np.arange(s // 2), 2)
    rows = norm_rotary.rotary_table(s, d, 1e6, positions)

    def kernel(x, scale):
        return norm_rotary.norm_rotary(x, scale, rows, heads, 1e-6)

    def reference(x, scale):
        u = x.reshape(b, s, heads, d).astype(jnp.float32)
        u = u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True) + 1e-6)
        return attention.rotary_embedding(u * scale, 1e6, positions) \
            .reshape(x.shape)

    def pulled(f):
        return jax.jit(lambda x, scale: jax.vjp(f, x, scale)[1](
            cotangent.astype(f(x, scale).dtype)))

    tag = f"b{b}s{s}h{heads}d{d}"
    _check(table, f"norm_rotary fwd {tag}", jax.jit(kernel)(x, scale),
           jax.jit(reference)(x, scale), BF16_RTOL)
    _check(table, f"norm_rotary grad {tag}", pulled(kernel)(x, scale),
           pulled(reference)(x, scale), BF16_RTOL)


def phase_kernels() -> list:
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import autotune
    from analytics_zoo_tpu.ops import embedding_bag as eb
    from analytics_zoo_tpu.ops import paged_attention as pa

    table: list = []
    _flash_checks(table, BERT_BATCH, BERT_SEQ, 12, 64, causal=False)
    _flash_checks(table, 2, 2048, 8, 128, causal=True)
    # both operand layouts with a key/value head read by its group
    _flash_checks(table, 2, 2048, 8, 128, causal=True, kv_heads=2)
    _flash_checks(table, 2, 2048, 8, 64, causal=True, kv_heads=2)
    _norm_rotary_checks(table, 2, 2048, 8)

    def both_paths(fn, *args):
        """``fn(*args, use_kernel)`` jitted with the kernel pinned on, then
        off (the pure-jax reference)."""
        return [jax.jit(lambda *a, uk=uk: fn(*a, uk))(*args)
                for uk in (True, False)]

    key = jax.random.PRNGKey(1)
    ncf_shapes = ((NCF_USERS + 1, 20), (NCF_ITEMS + 1, 20))
    tables = tuple(jax.random.normal(jax.random.fold_in(key, t), shape)
                   for t, shape in enumerate(ncf_shapes))
    ids = jnp.stack([jax.random.randint(jax.random.fold_in(key, 10 + t),
                                        (NCF_BATCH,), 0, vocab)
                     for t, (vocab, _) in enumerate(ncf_shapes)], axis=1)
    for combine in ("concat", "mul"):       # NCF's MLP tower and GMF branch
        _check(table, f"fused lookup {combine} ncf b{NCF_BATCH}",
               *both_paths(lambda ts, ii, uk: eb.fused_embedding_lookup(
                   ts, ii, combine, use_kernel=uk), tables, ids), 0.0)
    bag_table = jax.random.normal(key, (1000, 128))
    bag_ids = jax.random.randint(jax.random.fold_in(key, 20), (256, 8),
                                 0, 1000)
    bag_len = jax.random.randint(jax.random.fold_in(key, 21), (256,), 0, 9)
    _check(table, "embedding bag mean 1000x128 b256 l8",
           *both_paths(lambda t, ii, ln, uk: eb.embedding_bag(
               t, ii, ln, "mean", use_kernel=uk),
               bag_table, bag_ids, bag_len), 0.0)

    page_shape = dict(batch=4, width=8, page_size=16, dim=128, n_pages=64)
    for dtype in (jnp.float32, jnp.int8):
        name = jnp.dtype(dtype).name
        pool, ptable, lengths, scales = pa._synth_args(dtype=dtype,
                                                       **page_shape)
        _check(table, f"paged_gather {name}",
               *both_paths(lambda p, t, ln, sc, uk: pa.paged_gather_pinned(
                   p, t, ln, sc, use_kernel=uk),
                   pool, ptable, lengths, scales), 0.0)
        q = jax.random.normal(jax.random.fold_in(key, 30),
                              (page_shape["batch"], page_shape["dim"]))
        _check(table, f"paged_attention {name}",
               *both_paths(lambda q, kp, vp, t, ln, ks, vs, uk:
                           pa.paged_attention(q, kp, vp, t, ln, k_scales=ks,
                                              v_scales=vs, use_kernel=uk),
                           q, pool, pool[::-1], ptable, lengths, scales,
                           scales), 1e-5)

    # one synchronous tune per family: on the chip a candidate that fails
    # raises, and a verdict must say where it was measured
    verdicts = {
        "flash_attention": autotune.tune_attention(
            BERT_BATCH, BERT_SEQ, 12, 64, dtype=jnp.bfloat16),
        "fused_lookup": eb.tune_fused_lookup(ncf_shapes, NCF_BATCH),
        "embedding_bag": eb.tune_bag(1000, 128, 256, 8),
        "paged_gather": pa.tune_paged_gather(**page_shape),
        "paged_attention": pa.tune_paged_attention(**page_shape),
    }
    for family, rec in verdicts.items():
        check(rec["errors"] == {} and rec["platform"] == "tpu"
              and rec["best"] is not None, f"{family}: {rec}")
        table.append({"kernel": f"tune {family}", "best": rec["best"],
                      "use_kernel": rec["use_kernel"],
                      "best_ms": rec["best_ms"],
                      "reference_ms": rec["reference_ms"]})
        print(f"  tune {family}: best {rec['best']} {rec['best_ms']} ms, "
              f"reference {rec['reference_ms']} ms, use_kernel "
              f"{rec['use_kernel']}", flush=True)
    return table


# --------------------------------------------------------------- four chips

def _bytes_in_use() -> list:
    import jax
    return [int(d.memory_stats()["bytes_in_use"]) for d in jax.devices()]


def _assert_spans_mesh(tree, n: int, what: str):
    """Every leaf is laid out over all ``n`` devices, and at least one is
    actually split (not merely replicated)."""
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    split = 0
    for leaf in leaves:
        check(len(leaf.sharding.device_set) == n,
              f"{what}: a leaf sits on {len(leaf.sharding.device_set)} "
              f"devices")
        split += not leaf.sharding.is_fully_replicated
    check(split, f"{what}: every leaf is replicated, nothing is sharded")
    return split, len(leaves)


def phase_multichip():
    import jax
    import numpy as np
    from analytics_zoo_tpu.inference import InferenceModel
    from analytics_zoo_tpu.learn.estimator import Estimator
    from analytics_zoo_tpu.models.recommendation import NeuralCF
    from analytics_zoo_tpu.text.bert import bert_tp_rules

    n = len(jax.devices())
    if n < 4:
        print(f"multichip: skipped ({n} device)", flush=True)
        return
    gc.collect()        # the earlier phases' arrays go before the baseline
    before = _bytes_in_use()
    rng = np.random.default_rng(2)

    ncf = NeuralCF(user_count=NCF_USERS, item_count=NCF_ITEMS,
                   class_num=NCF_CLASSES)
    ncf.model.set_strategy("dp2,tp2", param_rules=NeuralCF.tp_param_rules())
    ncf.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    losses = _assert_losses(
        ncf.fit(*_ncf_batches(rng), batch_size=NCF_BATCH, nb_epoch=2),
        "ncf dp2,tp2")
    split, total = _assert_spans_mesh(
        ncf.model.estimator._state["params"], 4, "ncf dp2,tp2")
    print(f"ncf dp2,tp2: losses {losses}, {split}/{total} leaves split",
          flush=True)

    module, cfg = _bert_classifier()
    x, y = _bert_batches(rng, cfg)
    est = Estimator.from_flax(
        model=module, loss="sparse_categorical_crossentropy_logits",
        optimizer="adam", sample_input=x[:2], strategy="fsdp4")
    losses = _assert_losses(
        est.fit((x, y), epochs=2, batch_size=BERT_BATCH), "bert fsdp4")
    split, total = _assert_spans_mesh(est._state["params"], 4, "bert fsdp4")
    print(f"bert-base fsdp4: losses {losses}, {split}/{total} leaves split",
          flush=True)

    params = module.init(jax.random.PRNGKey(0), x[:1])
    im = InferenceModel().load_flax(module, x[:1], params=params)
    im.shard("tp4", param_rules=bert_tp_rules())
    got = np.asarray(im.predict(x[:BERT_BATCH]))
    want = np.asarray(jax.jit(lambda p, a: module.apply(p, a))(
        params, x[:BERT_BATCH]))
    err, top = _max_err(got, want)
    check(err <= BF16_RTOL * max(1.0, top),
          f"tp4 predict differs from the unsharded forward by {err}")
    info = im.shard_info()
    check(info["n_shards"] == 4, f"{info['n_shards']} shards")
    check(max(info["shard_hbm_bytes"].values()) < info["total_param_bytes"],
          "one device holds the whole model")
    print(f"bert-base tp4 predict: max |err| {err:.2e}, largest shard "
          f"{max(info['shard_hbm_bytes'].values()) / 2**20:.0f} MiB of "
          f"{info['total_param_bytes'] / 2**20:.0f} MiB", flush=True)

    after = _bytes_in_use()
    grew = [b - a for a, b in zip(before, after)]
    check(all(g > 0 for g in grew),
          f"bytes_in_use did not grow on every device: {grew}")
    print(f"multichip: bytes_in_use grew by "
          f"{[round(g / 2**20) for g in grew]} MiB per device", flush=True)


# --------------------------------------------------------------------- main

def main():
    device = preconditions()
    import jax
    import jaxlib
    from importlib.metadata import version
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.common import profiling

    # verdicts are measured in this run, never read from a file that came
    # along with the tree
    verdict_path = os.path.join(profiling.DUMP_DIR,
                                "chip_smoke_autotune.json")
    if os.path.exists(verdict_path):
        os.unlink(verdict_path)
    os.environ["ZOO_AUTOTUNE_CACHE"] = verdict_path

    _listen_for_setup_time()
    t0 = time.perf_counter()
    init_orca_context("local")
    cache_dir = jax.config.jax_compilation_cache_dir
    print(f"platform {device['platform']}, device_kind {device['kind']}, "
          f"{device['count']} device(s); jax {jax.__version__}, jaxlib "
          f"{jaxlib.__version__}, libtpu {version('libtpu')}; compile cache "
          f"{cache_dir} ({_cache_entries(cache_dir)} entries)", flush=True)

    report: dict = {}
    with Phase("trainer", cache_dir, report):
        phase_trainer()
    with Phase("server", cache_dir, report):
        phase_server()
    with Phase("kernels", cache_dir, report):
        kernels = phase_kernels()
    with Phase("multichip", cache_dir, report):
        phase_multichip()

    print(json.dumps({"phases": report, "kernels": kernels,
                      "wall_s": round(time.perf_counter() - t0, 1)}),
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
