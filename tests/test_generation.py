"""Bucketed autoregressive decode (ISSUE 14).

The load-bearing claim is the parity one: the decoder scan is strictly
causal in time, so a decode buffer padded to the seq-length rung must be
**bitwise identical** to the exact-length unpadded reference — across
tail lengths (live length strictly inside a rung) and rung-growth
boundaries. Everything else (KV-cache rung math, feedback modes, the
decode-steps counter) pins the machinery around that claim.
"""

import numpy as np
import pytest

from analytics_zoo_tpu.common import compile_ahead, telemetry
from analytics_zoo_tpu.inference import generation


def _decode_steps_total() -> float:
    val = telemetry.snapshot().get("zoo_decode_steps_total", 0.0)
    return float(val if isinstance(val, (int, float)) else 0.0)


# ------------------------------------------------------------ ladder

def test_seq_ladder_bounds():
    lad = generation.seq_ladder(33, min_rung=2)
    assert lad.rungs[0] == 2
    assert lad.rungs[-1] >= 33
    # a short generation must not be forced onto a tall bottom rung
    assert generation.seq_ladder(4).rungs[0] <= 4


# ---------------------------------------------------------- KV cache

def test_kv_cache_rung_growth_and_zero_tail():
    lad = compile_ahead.BucketLadder(2, 16)
    c = generation.BucketedKVCache(3, 5, lad)
    assert c.view().shape == (3, 2, 5)
    rungs = []
    for i in range(9):
        c.append(np.full((3, 5), float(i + 1), np.float32))
        rungs.append(c.rung)
    # power-of-two rung growth — never a per-step shape
    assert rungs == [2, 2, 4, 4, 8, 8, 8, 8, 16]
    v = c.view()
    assert v.shape == (3, 16, 5)
    assert np.all(v[:, 9:, :] == 0.0)       # zeros past the live length
    assert np.all(v[:, 8, :] == 9.0)        # last live position intact


def test_kv_cache_without_ladder_is_exact_length():
    c = generation.BucketedKVCache(2, 3)
    for i in range(5):
        c.append(np.zeros((2, 3), np.float32))
        assert c.rung == max(1, i + 1)      # exact shapes: parity baseline


# ------------------------------------------------------------ parity

@pytest.fixture(scope="module")
def s2s():
    from analytics_zoo_tpu.models import Seq2Seq
    return Seq2Seq(input_dim=3, output_dim=2, hidden_size=8,
                   rnn_type="gru", encoder_seq_len=4, decoder_seq_len=4)


@pytest.fixture(scope="module")
def s2s_inputs():
    rng = np.random.RandomState(0)
    enc = rng.randn(2, 4, 3).astype(np.float32)
    start = np.zeros((2, 2), np.float32)
    return enc, start


# 1: single step at the bottom rung; 3/4: tail inside rung 4 and exactly
# full; 5: the 4→8 growth boundary; 9: two growths with a final tail
@pytest.mark.parametrize("steps", [1, 3, 4, 5, 9])
def test_rung_padded_decode_is_bitwise_equal(s2s, s2s_inputs, steps):
    enc, start = s2s_inputs

    def fn(e, d):
        return s2s.predict((e, d))

    lad = generation.seq_ladder(steps + 1, min_rung=2)
    padded = generation.decode_loop(fn, enc, start, steps, ladder=lad)
    exact = generation.decode_loop(fn, enc, start, steps, ladder=None)
    assert padded.shape == (2, steps, 2)
    # bitwise, not allclose: causality means the rung's zero tail cannot
    # perturb a single ulp of the live positions
    assert np.array_equal(padded, exact)


def test_greedy_parity_across_growth_boundary(s2s, s2s_inputs):
    enc, start = s2s_inputs

    def fn(e, d):
        return s2s.predict((e, d))

    lad = generation.seq_ladder(8, min_rung=2)
    padded = generation.decode_loop(fn, enc, start, 6, ladder=lad,
                                    mode="greedy")
    exact = generation.decode_loop(fn, enc, start, 6, ladder=None,
                                   mode="greedy")
    assert np.array_equal(padded, exact)


# ------------------------------------------------------------- modes

def test_greedy_feedback_is_one_hot(s2s, s2s_inputs):
    enc, start = s2s_inputs
    out = generation.decode_loop(
        lambda e, d: s2s.predict((e, d)), enc, start, 4,
        ladder=generation.seq_ladder(5, min_rung=2), mode="greedy")
    flat = out.reshape(-1, out.shape[-1])
    assert np.all(np.isin(flat, (0.0, 1.0)))
    assert np.all(flat.sum(axis=-1) == 1.0)


def test_sample_mode_is_seed_deterministic(s2s, s2s_inputs):
    enc, start = s2s_inputs

    def run(seed):
        return generation.decode_loop(
            lambda e, d: s2s.predict((e, d)), enc, start, 6,
            ladder=generation.seq_ladder(7, min_rung=2), mode="sample",
            temperature=0.7, seed=seed)

    assert np.array_equal(run(5), run(5))


def test_sample_token_ids_gumbel_stream_contract():
    vec = np.random.default_rng(0).normal(size=(4, 6))
    a, b = np.random.default_rng(9), np.random.default_rng(9)
    ids = generation.sample_token_ids(vec, 0.7, a)
    assert ids.shape == (4,)
    # exactly ONE uniform draw of vec.shape per call — the contract the
    # step scheduler's per-sequence rng streams rest on
    b.random(vec.shape)
    assert a.bit_generator.state == b.bit_generator.state
    ids2 = generation.sample_token_ids(vec, 0.7, np.random.default_rng(9))
    assert np.array_equal(ids, ids2)


def test_sample_vectorization_matches_per_row_reference():
    vec = np.random.default_rng(4).normal(size=(5, 7))
    u = np.random.default_rng(11).random(vec.shape)
    u = np.maximum(u, np.finfo(np.float64).tiny)
    want = np.array([np.argmax(vec[i] / 0.7 - np.log(-np.log(u[i])))
                     for i in range(vec.shape[0])])
    got = generation.sample_token_ids(vec, 0.7, np.random.default_rng(11))
    assert np.array_equal(got, want)


def test_sample_low_temperature_collapses_to_argmax():
    vec = np.random.default_rng(1).normal(size=(8, 5))
    ids = generation.sample_token_ids(vec, 1e-9, np.random.default_rng(3))
    assert np.array_equal(ids, np.argmax(vec, axis=-1))


def test_feedback_rows_sample_is_seeded_one_hot():
    vec = np.random.default_rng(2).normal(size=(3, 4)).astype(np.float32)
    r1 = generation.feedback_rows(
        vec, "sample", 0.5, np.random.default_rng(7))
    r2 = generation.feedback_rows(
        vec, "sample", 0.5, np.random.default_rng(7))
    assert np.array_equal(r1, r2)
    assert np.all(np.isin(r1, (0.0, 1.0)))
    assert np.all(r1.sum(axis=-1) == 1.0)


def test_bad_mode_and_steps_raise(s2s_inputs):
    enc, start = s2s_inputs
    fn = lambda e, d: np.zeros((e.shape[0], d.shape[1], 2), np.float32)
    with pytest.raises(ValueError):
        generation.decode_loop(fn, enc, start, 4, mode="beam")
    with pytest.raises(ValueError):
        generation.decode_loop(fn, enc, start, 0)


# ------------------------------------------------- model + telemetry

def test_seq2seq_infer_rides_the_bucketed_loop(s2s, s2s_inputs):
    enc, start = s2s_inputs
    out = s2s.infer(enc, start_sign=start, max_seq_len=6)
    assert out.shape == (2, 5, 2)
    # degenerate request: nothing to generate
    assert s2s.infer(enc, start_sign=start, max_seq_len=1).shape == (2, 0, 2)


def test_decode_steps_counter_and_rung_gauge(s2s, s2s_inputs):
    enc, start = s2s_inputs
    before = _decode_steps_total()
    generation.decode_loop(
        lambda e, d: s2s.predict((e, d)), enc, start, 4,
        ladder=generation.seq_ladder(5, min_rung=2))
    # one increment per generated position per record in the batch
    assert _decode_steps_total() - before == enc.shape[0] * 4
    assert float(telemetry.snapshot().get("zoo_kv_cache_rung", 0.0)) >= 2


def test_decode_spans_land_on_the_trace(s2s, s2s_inputs):
    enc, start = s2s_inputs
    generation.decode_loop(
        lambda e, d: s2s.predict((e, d)), enc, start, 3,
        ladder=generation.seq_ladder(4, min_rung=2),
        trace_ids=("gen-span-test",))
    spans = telemetry.get_tracer().get("gen-span-test")
    names = {s.name for s in spans}
    assert {"decode_step_1", "decode_step_2", "decode_step_3"} <= names
    assert all(s.parent == "device" for s in spans
               if s.name.startswith("decode_step_"))


# ------------------------------------- InferenceModel: warmed grid, draft

def _compiles_on_the_hot_path() -> float:
    """Dispatches of the model's forward that found no executable built
    ahead of time: compiled in band, or left to plain jit."""
    snap = telemetry.snapshot()
    return sum(float(snap.get(name, {}).get("fn=inference_model", 0.0))
               for name in ("zoo_compile_cache_misses_total",
                            "zoo_jit_cache_misses_total"))


def _counter(name) -> float:
    val = telemetry.snapshot().get(name, 0.0)
    return float(val if isinstance(val, (int, float)) else 0.0)


def test_warmed_decode_grid_never_recompiles_and_self_draft_accepts_all():
    """``warm_decode`` builds the (batch rung x seq rung) grid ahead of
    time, so a decode loop that grows through the seq rungs compiles
    nothing; the same model drafting for itself proposes tokens that are
    all accepted, and the speculative result is the plain greedy loop's
    bit for bit."""
    from analytics_zoo_tpu.inference import InferenceModel
    from analytics_zoo_tpu.models import Seq2Seq

    batch, steps = 4, 8
    m = Seq2Seq(input_dim=8, output_dim=8, hidden_size=16, rnn_type="gru",
                encoder_seq_len=8, decoder_seq_len=4)
    im = InferenceModel().load_zoo(m)
    rng = np.random.default_rng(7)
    enc = rng.standard_normal((batch, 8, 8)).astype(np.float32)
    start = np.zeros((batch, 8), np.float32)
    # one predict registers the two-input spec the grid is built from
    im.predict((enc, np.zeros((batch, 1, 8), np.float32)))
    im.set_ladder(compile_ahead.BucketLadder(batch, batch))
    im.warm_decode(steps + 1, block=True)

    def step(e, d):
        return np.asarray(im.predict_fetch(im.predict_async((e, d))))

    base = _compiles_on_the_hot_path()
    plain = generation.decode_loop(step, enc, start, steps,
                                   ladder=generation.seq_ladder(steps + 1),
                                   mode="greedy")
    assert plain.shape == (batch, steps, 8)
    assert _compiles_on_the_hot_path() == base, \
        "the decode loop met a shape warm_decode had not built"

    im.warm_decode(steps + 1, verify_k=4, block=True)
    proposed0 = _counter("zoo_spec_proposed_total")
    accepted0 = _counter("zoo_spec_accepted_total")
    spec = im.generate(enc, start, steps, mode="greedy", draft=im, spec_k=4)
    assert np.array_equal(spec, plain)
    proposed = _counter("zoo_spec_proposed_total") - proposed0
    assert proposed > 0
    assert _counter("zoo_spec_accepted_total") - accepted0 == proposed
