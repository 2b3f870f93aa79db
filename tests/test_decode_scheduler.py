"""Step-level decode scheduler: paged KV allocator/cache units, bitwise
interleaved-vs-isolated parity (mid-flight admission, step-boundary
pauses, page recycling across lengths), speculative accept/reject vs the
greedy reference, and pool admission control."""

import numpy as np
import pytest

from analytics_zoo_tpu.common import telemetry
from analytics_zoo_tpu.inference import generation
from analytics_zoo_tpu.inference.decode_scheduler import (
    DecodeScheduler, PagedKVAllocator, PagedKVCache, PagePoolExhausted,
)

DIM = 6


def _step_fn(scale=1.0):
    """Deterministic, strictly causal, row-independent decoder: output at
    position t mixes enc with the cumulative sum of dec[:, :t+1] — the
    properties the interleaving parity claim rests on."""
    w = np.random.default_rng(0).normal(size=(DIM, DIM)).astype(np.float32)

    def fn(enc, dec):
        csum = np.cumsum(np.asarray(dec, np.float32), axis=1)
        return np.tanh(scale * (csum @ w) + np.asarray(
            enc, np.float32)[:, None, :])
    return fn


def _enc(seed, n=1):
    rows = np.random.default_rng(seed).normal(
        size=(n, DIM)).astype(np.float32)
    return rows if n > 1 else rows[0]


def _start():
    s = np.zeros(DIM, np.float32)
    s[0] = 1.0
    return s


def _reference(fn, enc_row, steps, **kw):
    """Isolated whole-loop reference for a single sequence."""
    return generation.decode_loop(
        fn, enc_row[None], _start()[None], steps, ladder=None, **kw)[0]


# ------------------------------------------------------------- allocator

def test_allocator_sizing_and_pages_for():
    alloc = PagedKVAllocator.for_grid(4, 17, DIM, page_size=8)
    assert alloc.n_pages == 4 * 3          # ceil(17/8) per sequence
    assert alloc.pages_for(0) == 0
    assert alloc.pages_for(1) == 1
    assert alloc.pages_for(8) == 1
    assert alloc.pages_for(9) == 2


def test_allocator_zeroes_recycled_pages_and_syncs_gauges():
    alloc = PagedKVAllocator(4, 2, DIM)
    pages = alloc.alloc_pages(2)
    alloc._pool[pages[0]].fill(7.0)
    alloc.free_pages(pages)
    again = alloc.alloc_pages(4)
    assert all(not alloc._pool[p].any() for p in again)
    snap = telemetry.snapshot()
    assert float(snap["zoo_kv_pages_in_use"]) == 4.0
    assert float(snap["zoo_kv_pages_free"]) == 0.0


def test_allocator_exhaustion_vs_growth():
    alloc = PagedKVAllocator(4, 2, DIM)
    held = alloc.alloc_pages(3)
    # contention: another sequence holds the pages -> defer admission
    with pytest.raises(PagePoolExhausted):
        alloc.alloc_pages(2)
    alloc.free_pages(held)
    # a single request larger than the whole pool is capacity planning:
    # the pool grows instead of raising
    big = alloc.alloc_pages(6)
    assert len(big) == 6 and alloc.n_pages == 6


# ----------------------------------------------------------------- cache

def test_cache_append_truncate_gather_close():
    alloc = PagedKVAllocator(8, 2, DIM)
    cache = PagedKVCache(alloc, alloc.alloc_pages(2))
    rows = np.eye(DIM, dtype=np.float32)[:4]
    cache.append_block(rows[:3])
    assert cache.length == 3
    assert cache.token_id(1) == 1
    assert np.array_equal(cache.row(2), rows[2])
    # growth past the admission reservation allocs straight into _pages
    cache.append(rows[3])
    cache.append(rows[0])
    assert cache.length == 5 and cache.capacity == 6
    dst = np.full((8, DIM), 9.0, np.float32)
    dst[:] = 0.0
    cache.gather_into(dst)
    assert np.array_equal(dst[:3], rows[:3])
    assert not dst[5:].any()                 # causal zero tail
    cache.truncate(2)
    assert cache.length == 2
    dst[:] = 0.0
    cache.gather_into(dst)
    assert not dst[2:].any()                 # truncated drafts zeroed
    cache.close()
    cache.close()                            # idempotent
    assert alloc.n_free == alloc.n_pages


# ------------------------------------------------- interleaving parity

def test_scheduler_greedy_matches_isolated_reference_bitwise():
    fn = _step_fn()
    sched = DecodeScheduler(fn, max_batch=4, max_seq=16, page_size=4)
    seqs = [sched.admit(_enc(i), _start(), 5 + i, mode="greedy")
            for i in range(3)]
    sched.drain()
    for i, s in enumerate(seqs):
        ref = _reference(fn, _enc(i), 5 + i, mode="greedy")
        assert np.array_equal(s.result, ref)
    # every page back in the pool after retirement
    assert sched.allocator.n_free == sched.allocator.n_pages


def test_mid_flight_admission_is_invisible_bitwise():
    fn = _step_fn()
    sched = DecodeScheduler(fn, max_batch=4, max_seq=32, page_size=4)
    a = sched.admit(_enc(1), _start(), 10, mode="greedy")
    for _ in range(4):                       # a is mid-generation...
        sched.step()
    b = sched.admit(_enc(2), _start(), 6, mode="greedy")
    sched.drain()
    assert np.array_equal(a.result, _reference(fn, _enc(1), 10,
                                               mode="greedy"))
    assert np.array_equal(b.result, _reference(fn, _enc(2), 6,
                                               mode="greedy"))


def test_step_boundary_pauses_are_invisible_bitwise():
    # the engine preempts between steps — a paused-and-resumed schedule
    # must produce exactly what an uninterrupted drain produces
    fn = _step_fn()
    paused = DecodeScheduler(fn, max_batch=4, max_seq=16, page_size=4)
    straight = DecodeScheduler(fn, max_batch=4, max_seq=16, page_size=4)
    p = [paused.admit(_enc(i), _start(), 7, mode="greedy")
         for i in range(2)]
    s = [straight.admit(_enc(i), _start(), 7, mode="greedy")
         for i in range(2)]
    while paused.live:
        paused.step()                        # "preemption" = caller pause
        # arbitrary interleaved work happens here in the engine
    straight.drain()
    for x, y in zip(p, s):
        assert np.array_equal(x.result, y.result)


def test_page_recycling_across_lengths():
    fn = _step_fn()
    # pool holds exactly two worst-case sequences (6 pages of 4)
    alloc = PagedKVAllocator.for_grid(2, 12, DIM, page_size=4)
    sched = DecodeScheduler(fn, max_batch=2, max_seq=11, page_size=4,
                            allocator=alloc, spec_k=0)
    short = sched.admit(_enc(3), _start(), 2, mode="greedy")
    long = sched.admit(_enc(4), _start(), 11, mode="greedy")
    with pytest.raises(PagePoolExhausted):
        sched.admit(_enc(5), _start(), 11, mode="greedy")
    while not short.done:
        sched.step()
    # the short retirement freed pages mid-flight of the long one
    third = sched.admit(_enc(5), _start(), 4, mode="greedy")
    sched.drain()
    assert np.array_equal(short.result, _reference(fn, _enc(3), 2,
                                                   mode="greedy"))
    assert np.array_equal(long.result, _reference(fn, _enc(4), 11,
                                                  mode="greedy"))
    assert np.array_equal(third.result, _reference(fn, _enc(5), 4,
                                                   mode="greedy"))
    assert alloc.n_free == alloc.n_pages


def test_chunked_prefill_matches_isolated_scheduler():
    fn = _step_fn()
    prefill = np.random.default_rng(8).normal(
        size=(9, DIM)).astype(np.float32)

    def run(extra_load):
        sched = DecodeScheduler(fn, max_batch=4, max_seq=32, page_size=4,
                                prefill_chunk=4)
        if extra_load:
            sched.admit(_enc(6), _start(), 12, mode="greedy")
        seq = sched.admit(_enc(7), prefill, 5, mode="greedy")
        sched.drain()
        return seq.result

    assert np.array_equal(run(True), run(False))


def test_sample_mode_rng_is_per_sequence():
    fn = _step_fn()
    sched = DecodeScheduler(fn, max_batch=4, max_seq=16, page_size=4)
    seqs = [sched.admit(_enc(i), _start(), 6, mode="sample",
                        temperature=0.7, seed=100 + i)
            for i in range(3)]
    sched.drain()
    for i, s in enumerate(seqs):
        ref = _reference(fn, _enc(i), 6, mode="sample", temperature=0.7,
                         seed=100 + i)
        assert np.array_equal(s.result, ref)


# ------------------------------------------------- speculative decoding

def test_speculative_with_perfect_draft_is_bitwise_greedy():
    fn = _step_fn()
    sched = DecodeScheduler(fn, max_batch=4, max_seq=16, page_size=4,
                            draft_fn=fn, spec_k=3)
    seqs = [sched.admit(_enc(i), _start(), 8, mode="greedy")
            for i in range(2)]
    sched.drain()
    for i, s in enumerate(seqs):
        assert np.array_equal(s.result,
                              _reference(fn, _enc(i), 8, mode="greedy"))
    # a perfect draft never mismatches
    assert sched.spec_accept_ratio == 1.0
    # and accepted tokens cost no extra target steps: 8 tokens in
    # ceil(8 / (spec_k + 1)) wide steps, not 8
    assert sched.steps_run == 2
    assert sched.allocator.n_free == sched.allocator.n_pages


def test_speculative_with_adversarial_draft_still_bitwise_greedy():
    fn = _step_fn()
    bad = lambda enc, dec: -fn(enc, dec)     # disagrees everywhere
    sched = DecodeScheduler(fn, max_batch=4, max_seq=16, page_size=4,
                            draft_fn=bad, spec_k=3)
    s = sched.admit(_enc(9), _start(), 8, mode="greedy")
    sched.drain()
    assert np.array_equal(s.result, _reference(fn, _enc(9), 8,
                                               mode="greedy"))
    assert sched.spec_accept_ratio == 0.0
    assert sched.allocator.n_free == sched.allocator.n_pages


def test_speculative_skips_sample_mode_sequences():
    # clean fallback: sampled sequences take the plain one-token step
    # even with a draft configured, and their rng stream is unchanged
    fn = _step_fn()
    sched = DecodeScheduler(fn, max_batch=4, max_seq=16, page_size=4,
                            draft_fn=fn, spec_k=3)
    s = sched.admit(_enc(2), _start(), 6, mode="sample", temperature=0.7,
                    seed=42)
    sched.drain()
    ref = _reference(fn, _enc(2), 6, mode="sample", temperature=0.7,
                     seed=42)
    assert np.array_equal(s.result, ref)
    assert sched.spec_accept_ratio == 0.0    # nothing was proposed


def test_spec_metrics_land_on_the_registry():
    fn = _step_fn()
    sched = DecodeScheduler(fn, max_batch=2, max_seq=16, page_size=4,
                            draft_fn=fn, spec_k=2)
    sched.admit(_enc(1), _start(), 6, mode="greedy")
    sched.drain()
    snap = telemetry.snapshot()
    assert float(snap["zoo_spec_proposed_total"]) > 0
    assert float(snap["zoo_spec_accepted_total"]) > 0
    assert float(snap["zoo_spec_accept_ratio"]) == 1.0


# ------------------------------------------- engine preemption seam

def _preemptions_total():
    fam = telemetry.snapshot().get("zoo_decode_preemptions_total", {})
    if not isinstance(fam, dict):
        return float(fam or 0.0)
    return float(sum(fam.values()))


def test_engine_defers_decode_to_hotter_lane_with_starvation_floor():
    """The engine's per-step preemption: a waiting record on a lane with
    a strictly lower credit/weight ratio defers the decode step (counted
    on zoo_decode_preemptions_total), and the starvation floor forces a
    step through after DECODE_STARVATION_FLOOR consecutive deferrals."""
    from analytics_zoo_tpu.serving.engine import ClusterServing

    eng = ClusterServing(object(), 0, warmup=False)
    sched = DecodeScheduler(_step_fn(), max_batch=2, max_seq=16,
                            page_size=4)
    seq = sched.admit(_enc(1), _start(), 8, mode="greedy")
    eng._decode_sched = sched
    eng._gen_live[seq] = ("u1", ("XACK",), None, "batch", eng._conn_gen)
    # one interactive record waiting in the assembly bucket, its lane
    # ratio (0/4) strictly under the live decode lane's (5/1)
    eng._asm = [(1, "u2", {}, None, "interactive", 0.0, None, None)]
    eng._lane_credit.update({"interactive": 0.0, "batch": 5.0})
    before = _preemptions_total()
    for _ in range(eng.DECODE_STARVATION_FLOOR):
        assert eng._decode_tick(None) == 0
    assert sched.steps_run == 0                  # every tick deferred
    assert _preemptions_total() - before == eng.DECODE_STARVATION_FLOOR
    eng._decode_tick(None)                       # floor reached: step runs
    assert sched.steps_run == 1
    assert _preemptions_total() - before == eng.DECODE_STARVATION_FLOOR
    # with nothing waiting the decode never defers
    eng._asm = []
    eng._decode_tick(None)
    assert sched.steps_run == 2
    sched.abort_all()


def test_engine_answers_a_generate_flood_and_the_probes_between_its_steps():
    """Several generate records live in the engine's step scheduler at
    once while interactive predicts arrive on the same stream: every
    record of both lanes ends in a result of its own shape, none
    expires, and each generation is what the model generates alone."""
    from analytics_zoo_tpu.inference import InferenceModel
    from analytics_zoo_tpu.models import Seq2Seq
    from analytics_zoo_tpu.serving import (
        Broker, ClusterServing, InputQueue, OutputQueue,
    )

    n_gen, n_probe, steps = 6, 6, 8
    m = Seq2Seq(input_dim=8, output_dim=8, hidden_size=16, rnn_type="gru",
                encoder_seq_len=8, decoder_seq_len=4)
    im = InferenceModel().load_zoo(m)
    rng = np.random.default_rng(29)
    encs = rng.standard_normal((n_gen, 8, 8)).astype(np.float32)
    start = np.zeros(8, np.float32)
    probe_dec = np.zeros((4, 8), np.float32)
    with Broker.launch(backend="python") as broker, \
            ClusterServing(im, broker.port, batch_size=4, max_batch_size=4,
                           block_ms=10, warmup=False) as eng:
        in_q = InputQueue(port=broker.port)
        out_q = OutputQueue(port=broker.port)
        flood = list(in_q.enqueue_batch(
            ((f"fg{i}", {"x": encs[i], "start": start})
             for i in range(n_gen)),
            priority="batch", generate={"max_new_tokens": steps}))
        for i in range(n_probe):
            u = in_q.enqueue(f"fp{i}", priority="interactive",
                             deadline_ms=120_000.0,
                             a_enc=encs[i % n_gen], b_dec=probe_dec)
            r = out_q.query(u, timeout=120.0)
            assert r is not None and r.shape == (4, 8), u
        res = out_q.query_many(flood, timeout=120.0)
        assert eng.metrics()["records_expired"] == 0
    assert all(v is not None for v in res.values())
    alone = im.generate(encs, np.tile(start, (n_gen, 1)), steps)
    for i, u in enumerate(flood):
        assert res[u].shape == (steps, 8)
        np.testing.assert_allclose(res[u], alone[i], rtol=1e-5, atol=1e-5)


# ------------------------------------------- paged step seam (ISSUE 20)

def _paged_fn(fn):
    """Numpy seam with the contract of InferenceModel.paged_decode_step_fn:
    ``(enc, pool, scales, table, lengths) -> [rung, width*page_size, dim]``
    — gather the pages (dequantizing with the exact ``q*scale`` expression
    the allocator's read path uses), zero the causal tail, run the step."""
    def paged(enc, pool, scales, table, lengths):
        pool = np.asarray(pool)
        table = np.asarray(table)
        b, w = table.shape
        ps = pool.shape[1]
        rows = pool[table].astype(np.float32)            # [b, w, ps, d]
        if pool.dtype == np.int8:
            rows = rows * np.asarray(
                scales, np.float32)[table][:, :, None, None]
        dec = rows.reshape(b, w * ps, -1)
        pos = np.arange(w * ps)[None, :, None]
        dec = np.where(pos < np.asarray(lengths)[:, None, None], dec, 0.0)
        return fn(enc, dec)
    return paged


def _counter(name):
    val = telemetry.snapshot().get(name, 0.0)
    return float(val if isinstance(val, (int, float)) else 0.0)


def _paged_pair(fn, paged, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq", 16)
    kw.setdefault("page_size", 4)
    return DecodeScheduler(fn, paged_step_fn=_paged_fn(fn), paged=paged,
                           **kw)


def test_paged_force_matches_off_bitwise_with_midflight_admission():
    """The tentpole parity claim: forcing every wide step through the
    paged seam is bitwise invisible — across page-boundary lengths, seq
    rung growth and a mid-flight admission."""
    fn = _step_fn()
    results = {}
    for paged in ("off", "force"):
        sched = _paged_pair(fn, paged)
        a = sched.admit(_enc(1), _start(), 11, mode="greedy")  # 2→3 pages
        for _ in range(5):
            sched.step()
        b = sched.admit(_enc(2), _start(), 4, mode="greedy")   # boundary
        sched.drain()
        results[paged] = (a.result.copy(), b.result.copy())
    assert np.array_equal(results["force"][0], results["off"][0])
    assert np.array_equal(results["force"][1], results["off"][1])
    # and both equal the isolated whole-loop reference
    assert np.array_equal(results["force"][0],
                          _reference(fn, _enc(1), 11, mode="greedy"))


def test_paged_steps_count_and_fallback_counts(monkeypatch):
    fn = _step_fn()
    steps0, fall0 = (_counter("zoo_paged_attn_steps_total"),
                     _counter("zoo_paged_attn_fallback_total"))
    sched = _paged_pair(fn, "force")
    sched.admit(_enc(1), _start(), 4, mode="greedy")
    sched.drain()
    assert _counter("zoo_paged_attn_steps_total") > steps0
    # a seam configured but not dispatched (here: tuning disabled, so
    # "auto" can never see a winning verdict) counts the gather fallback
    monkeypatch.setenv("ZOO_AUTOTUNE", "off")
    sched = _paged_pair(fn, "auto")
    sched.admit(_enc(2), _start(), 4, mode="greedy")
    sched.drain()
    assert _counter("zoo_paged_attn_fallback_total") > fall0


def test_paged_recycling_with_lazy_zero_stays_bitwise():
    """After the first paged step the allocator stops zeroing recycled
    pages (the kernel's length mask is the hygiene): dirty pages flow
    back into new sequences and the outputs still match the reference
    bitwise, while the skip counter advances."""
    fn = _step_fn()
    sched = _paged_pair(fn, "force", max_batch=2, max_seq=11, spec_k=0)
    skip0 = _counter("zoo_kv_page_zeros_skipped_total")
    short = sched.admit(_enc(3), _start(), 2, mode="greedy")
    long = sched.admit(_enc(4), _start(), 11, mode="greedy")
    while not short.done:
        sched.step()
    assert sched.allocator.lazy_zero           # flipped by the first step
    third = sched.admit(_enc(5), _start(), 4, mode="greedy")  # dirty pages
    sched.drain()
    assert np.array_equal(short.result, _reference(fn, _enc(3), 2,
                                                   mode="greedy"))
    assert np.array_equal(long.result, _reference(fn, _enc(4), 11,
                                                  mode="greedy"))
    assert np.array_equal(third.result, _reference(fn, _enc(5), 4,
                                                   mode="greedy"))
    assert sched.allocator.zeros_skipped > 0
    assert _counter("zoo_kv_page_zeros_skipped_total") > skip0


def test_eager_zeroing_stays_default_without_paged_steps():
    # the gather fallback relies on pre-zeroed pages — lazy mode must
    # only ever engage once a kernel-masked step has actually run
    alloc = PagedKVAllocator(4, 2, DIM)
    assert not alloc.lazy_zero
    pages = alloc.alloc_pages(2)
    alloc._pool[pages[0]].fill(7.0)
    alloc.free_pages(pages)
    assert all(not alloc._pool[p].any() for p in alloc.alloc_pages(4))


def test_paged_auto_dispatch_consults_step_verdict(monkeypatch, tmp_path):
    from analytics_zoo_tpu.ops import autotune, paged_attention
    monkeypatch.setenv("ZOO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    autotune.reset_tuner()
    try:
        fn = _step_fn()
        sched = _paged_pair(fn, "auto", max_batch=2)
        seq = sched.admit(_enc(1), _start(), 6, mode="greedy")
        alloc = sched.allocator
        # seed a winning verdict for every step shape this drain can hit
        for want in range(1, sched.max_seq + 2):
            key = paged_attention.step_key(
                1, sched._seq_ladder.rung_for(want), sched.page_size,
                alloc.dim, alloc.n_pages, alloc.kv_dtype, seq.enc.shape)
            autotune.get_tuner().record(key, {
                "kernel": "paged_step", "best": "paged",
                "use_kernel": True, "best_ms": 1.0, "reference_ms": 2.0,
                "speedup": 2.0})
        steps0 = _counter("zoo_paged_attn_steps_total")
        sched.drain()
        assert _counter("zoo_paged_attn_steps_total") > steps0
        assert np.array_equal(seq.result,
                              _reference(fn, _enc(1), 6, mode="greedy"))
    finally:
        autotune.reset_tuner()
        autotune._pending.clear()


def test_paged_auto_miss_enqueues_tuning_thunk(monkeypatch, tmp_path):
    from analytics_zoo_tpu.ops import autotune
    monkeypatch.setenv("ZOO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    monkeypatch.setenv("ZOO_AUTOTUNE_ITERS", "1")
    autotune.reset_tuner()
    try:
        fn = _step_fn()
        sched = _paged_pair(fn, "auto", max_batch=2)
        seq = sched.admit(_enc(1), _start(), 3, mode="greedy")
        sched.drain()
        # every miss took the gather reference and queued a measurement
        assert np.array_equal(seq.result,
                              _reference(fn, _enc(1), 3, mode="greedy"))
        assert autotune.pending_count() > 0
        assert autotune.tune_pending() > 0       # warmup worker drains it
        assert autotune.pending_count() == 0
    finally:
        autotune.reset_tuner()
        autotune._pending.clear()


def test_tune_paged_records_verdict_at_live_shape(monkeypatch, tmp_path):
    from analytics_zoo_tpu.ops import autotune
    monkeypatch.setenv("ZOO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    monkeypatch.setenv("ZOO_AUTOTUNE_ITERS", "1")
    autotune.reset_tuner()
    try:
        fn = _step_fn()
        sched = _paged_pair(fn, "auto")
        sched.admit(_enc(1), _start(), 4, mode="greedy")
        rec = sched.tune_paged()
        assert rec is not None and rec["kernel"] == "paged_step"
        # never-slower invariant holds for the step verdict too
        if rec["use_kernel"]:
            assert rec["best_ms"] < rec["reference_ms"]
        else:
            assert rec["best_ms"] is None or \
                rec["best_ms"] >= rec["reference_ms"]
        sched.abort_all()
    finally:
        autotune.reset_tuner()
        autotune._pending.clear()


# --------------------------------------------------- int8 KV (ISSUE 20)

def test_int8_kv_greedy_is_bitwise_fp32(monkeypatch):
    """The greedy pin: one-hot feedback rows quantize exactly (argmax
    over a dequantized row picks the same token — the per-page scale is
    a single positive scalar), so int8-KV greedy generations equal the
    fp32 run bit for bit, through the paged seam and the gather path."""
    fn = _step_fn()
    fp32 = {}
    for paged in ("off", "force"):
        sched = _paged_pair(fn, paged)
        s = sched.admit(_enc(1), _start(), 9, mode="greedy")
        sched.drain()
        fp32[paged] = s.result.copy()
    monkeypatch.setenv("ZOO_KV_DTYPE", "int8")
    for paged in ("off", "force"):
        sched = _paged_pair(fn, paged)
        seq = sched.admit(_enc(1), _start(), 9, mode="greedy")
        sched.drain()
        assert sched.allocator.quantized
        assert np.array_equal(seq.result, fp32[paged]), (
            f"int8 KV diverged from fp32 under paged={paged}")
    assert np.array_equal(fp32["force"], fp32["off"])


def test_int8_kv_sample_mode_same_seed_matches_fp32(monkeypatch):
    fn = _step_fn()
    def run():
        sched = _paged_pair(fn, "force")
        s = sched.admit(_enc(2), _start(), 7, mode="sample",
                        temperature=0.8, seed=11)
        sched.drain()
        return s.result.copy()
    ref = run()
    monkeypatch.setenv("ZOO_KV_DTYPE", "int8")
    assert np.array_equal(run(), ref)


def test_int8_kv_raw_mode_accuracy_bound(monkeypatch):
    """Raw mode feeds real-valued rows back, so int8 KV genuinely loses
    precision — bounded by the per-page symmetric step (amax/127 per
    element, compounding through tanh's contraction)."""
    fn = _step_fn()
    def run():
        sched = _paged_pair(fn, "force")
        s = sched.admit(_enc(3), _start(), 8, mode="raw")
        sched.drain()
        return s.result.copy()
    ref = run()
    monkeypatch.setenv("ZOO_KV_DTYPE", "int8")
    got = run()
    assert not np.array_equal(got, ref)          # quantization is real
    np.testing.assert_allclose(got, ref, atol=0.05)


def test_int8_kv_doubles_admission_at_fixed_pool_bytes(monkeypatch):
    """The capacity claim: at a FIXED pool byte budget, int8 KV (1 byte
    per element + 8 bytes of scale/amax per page) admits at least twice
    the sequences fp32 does."""
    def admitted(kv_dtype):
        alloc = PagedKVAllocator.for_pool_bytes(
            8192, page_size=4, dim=DIM, kv_dtype=kv_dtype)
        sched = DecodeScheduler(_step_fn(), max_batch=64, max_seq=12,
                                page_size=4, allocator=alloc, spec_k=0)
        n = 0
        try:
            while True:
                sched.admit(_enc(n), _start(), 12, mode="greedy")
                n += 1
        except PagePoolExhausted:
            pass
        sched.abort_all()
        return n
    n_fp32 = admitted("float32")
    n_int8 = admitted("int8")
    assert n_fp32 >= 1
    assert n_int8 >= 2 * n_fp32


def test_int8_requant_on_amax_growth_keeps_rows_faithful():
    """A later, larger row on the same page forces a rescale: existing
    rows requantize to the new scale (counted on
    zoo_kv_quant_requants_total) and read back within one new step."""
    req0 = _counter("zoo_kv_quant_requants_total")
    alloc = PagedKVAllocator(2, 4, DIM, kv_dtype="int8")
    cache = PagedKVCache(alloc, alloc.alloc_pages(1))
    small = np.full(DIM, 0.01, np.float32)
    big = np.full(DIM, 1.27, np.float32)
    cache.append(small)
    cache.append(big)
    assert _counter("zoo_kv_quant_requants_total") > req0
    step = 1.27 / 127.0
    assert np.allclose(cache.row(0), small, atol=step / 2 + 1e-7)
    assert np.allclose(cache.row(1), big, atol=step / 2 + 1e-7)
    dst = np.zeros((4, DIM), np.float32)
    cache.gather_into(dst)
    assert np.allclose(dst[0], small, atol=step / 2 + 1e-7)
    assert not dst[2:].any()


def test_kv_pool_bytes_gauge_tracks_dtype(monkeypatch):
    PagedKVAllocator(4, 4, DIM)
    fp = float(telemetry.snapshot()["zoo_kv_quant_pool_bytes"])
    PagedKVAllocator(4, 4, DIM, kv_dtype="int8")
    q = float(telemetry.snapshot()["zoo_kv_quant_pool_bytes"])
    assert q < fp / 2                            # int8 halves the pool


def test_real_model_paged_seam_is_bitwise_gather(monkeypatch):
    """End to end through InferenceModel: the jitted paged forward
    (``paged_decode_step_fn`` — on-device gather fused under the decode
    step) against the host gather_into path, bitwise, fp32 and int8."""
    from analytics_zoo_tpu.inference import InferenceModel
    from analytics_zoo_tpu.models import Seq2Seq
    m = Seq2Seq(input_dim=4, output_dim=4, hidden_size=8, rnn_type="gru",
                encoder_seq_len=6, decoder_seq_len=4)
    im = InferenceModel().load_zoo(m)
    rng = np.random.default_rng(5)
    enc = rng.standard_normal((2, 6, 4)).astype(np.float32)
    start = np.zeros((2, 4), np.float32)
    start[:, 0] = 1.0
    im.predict((enc, np.zeros((2, 1, 4), np.float32)))

    def run(paged):
        sched = DecodeScheduler(
            im.decode_step_fn(), max_batch=2, max_seq=8, page_size=4,
            spec_k=0, paged_step_fn=im.paged_decode_step_fn(),
            paged=paged)
        seqs = [sched.admit(enc[i], start[i], 6, mode="greedy")
                for i in range(2)]
        sched.drain()
        return [s.result.copy() for s in seqs]

    base = run("off")
    got = run("force")
    for b, g in zip(base, got):
        assert np.array_equal(b, g)
    monkeypatch.setenv("ZOO_KV_DTYPE", "int8")
    for b, g in zip(base, run("force")):
        assert np.array_equal(b, g)              # greedy pin, real model


# ---------------------------------------------------- lifecycle & errors

def test_abort_all_frees_every_page():
    fn = _step_fn()
    sched = DecodeScheduler(fn, max_batch=4, max_seq=16, page_size=4)
    sched.admit(_enc(1), _start(), 8, mode="greedy")
    sched.admit(_enc(2), _start(), 8, mode="greedy")
    sched.step()
    dropped = sched.abort_all()
    assert len(dropped) == 2 and sched.live == 0
    assert sched.allocator.n_free == sched.allocator.n_pages


def test_admit_validates_inputs():
    sched = DecodeScheduler(_step_fn())
    with pytest.raises(ValueError):
        sched.admit(_enc(1), _start(), 0, mode="greedy")
    with pytest.raises(ValueError):
        sched.admit(_enc(1), _start(), 4, mode="beam")
    with pytest.raises(ValueError):
        sched.admit(_enc(1), np.zeros((2, 2, DIM)), 4)
