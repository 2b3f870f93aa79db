"""Block-diffusion training (``text/block_diffusion.py``): the static
masks in the flash kernels' tile table, the kernels under the
three-region mask, the module against the plain reference of the
benchmark's family ``sdar_moe`` at the tiny size in float32 on seeded
weights, what may and may not leak between the halves, the softmax
router's shares, the weighted loss."""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.common import telemetry
from analytics_zoo_tpu.learn import losses
from analytics_zoo_tpu.ops import attention as attention_lib
from analytics_zoo_tpu.ops import flash_attention as fa
from analytics_zoo_tpu.ops import moe as moe_lib
from analytics_zoo_tpu.text import block_diffusion
from benchmarks.harness import program
from benchmarks.harness.manifest import ROOT
from benchmarks.models import sdar_moe as model_lib
from benchmarks.references import adam
from benchmarks.references import sdar_moe as ref


@pytest.fixture(autouse=True)
def fresh_telemetry():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


def tiny_cfg(**over) -> dict:
    cfg = json.loads((ROOT / "benchmarks" / "configs"
                      / "sdar-30b-a3b.json").read_text())
    cfg.update(model_lib.TINY, compute_dtype="float32")
    cfg.update(over)
    return cfg


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def leaves(tree) -> dict:
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


# ------------------------------------------------- (a) the tile table

def dense_block_diffusion(L: int, B: int, n: int, noisy: bool = True):
    """ALLOWED pairs of the first ``n`` positions, position by position
    from the five lines of the issue; positions past the sequence (a
    padded tail) continue the clean half."""
    first_clean = L if noisy else 0
    allowed = np.zeros((n, n), bool)
    for i in range(n):
        for j in range(n):
            i_noisy, j_noisy = i < first_clean, j < first_clean
            bi = (i if i_noisy else i - first_clean) // B
            bj = (j if j_noisy else j - first_clean) // B
            if i_noisy:
                allowed[i, j] = (j_noisy and bj == bi) \
                    or (not j_noisy and bj < bi)
            else:
                allowed[i, j] = not j_noisy and bj <= bi
    return allowed


def dense_causal(sq: int, sk: int, nq: int, nk: int):
    return np.arange(nk)[None, :] <= np.arange(nq)[:, None] + (sk - sq)


def brute_force_kinds(allowed, bq, bk, kv_len):
    """{(qi, ki): kind} of every tile of the padded dense mask; padded
    keys are excluded."""
    allowed = allowed.copy()
    if kv_len is not None:
        allowed[:, kv_len:] = False
    out = {}
    for qi in range(allowed.shape[0] // bq):
        for ki in range(allowed.shape[1] // bk):
            tile = allowed[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            out[qi, ki] = fa.INTERIOR if tile.all() else \
                fa.DIAGONAL if tile.any() else fa.DEAD
    return out


#: id -> (L, B, block_q, block_k, noisy): the mask is over 2L rows
#: (noisy) or L; ragged where the rows are no multiple of a block
BLOCK_DIFFUSION_TABLES = {
    "aligned": (64, 4, 32, 32, True),
    "one_block_a_tile": (32, 8, 8, 8, True),
    "block_wider_than_tile": (32, 16, 8, 8, True),
    "unequal_blocks": (64, 4, 32, 16, True),
    "key_blocks_wider": (64, 4, 16, 64, True),
    "tile_straddles_the_halves": (48, 4, 32, 32, True),
    "ragged": (40, 4, 32, 32, True),
    "ragged_unequal": (44, 2, 48, 32, True),
    "block_of_one": (32, 1, 16, 16, True),
    "one_tile": (16, 4, 32, 32, True),
    "clean_half_alone": (64, 4, 16, 16, False),
    "clean_half_alone_ragged": (40, 8, 32, 16, False),
}


def _table_against(want, nq, nk, bq, bk, mask, kv_len):
    for key_major in (False, True):
        table = fa.tile_table(nq, nk, bq, bk, mask, kv_len,
                              key_major=key_major)
        rows = [tuple(r) for r in table.tolist()]
        live = [(qi, ki, kind) for (qi, ki), kind in want.items()
                if kind != fa.DEAD]
        order = (lambda r: (r[1], r[0])) if key_major else \
            (lambda r: (r[0], r[1]))
        assert [r for r in rows if r[2] != fa.DEAD] == sorted(live,
                                                              key=order)
        for r in rows:
            assert want[r[0], r[1]] == r[2]


@pytest.mark.parametrize("case", list(BLOCK_DIFFUSION_TABLES))
def test_block_diffusion_table_is_the_dense_masks_classification(case):
    L, B, bq, bk, noisy = BLOCK_DIFFUSION_TABLES[case]
    mask = fa.BlockDiffusionMask(L, B, noisy)
    n = mask.rows
    nq, nk = -(-n // bq), -(-n // bk)
    kv_len = n if n % bk else None
    allowed = dense_block_diffusion(L, B, max(nq * bq, nk * bk), noisy)
    want = brute_force_kinds(allowed[:nq * bq, :nk * bk], bq, bk, kv_len)
    _table_against(want, nq, nk, bq, bk, mask, kv_len)
    # the predicate itself, position by position, and the dense array
    np.testing.assert_array_equal(
        ~mask.excluded(np.arange(n)[:, None], np.arange(n)[None, :]),
        allowed[:n, :n])
    np.testing.assert_array_equal(np.asarray(mask.dense(n, n)),
                                  allowed[:n, :n])


def test_the_cells_launch_takes_80_tiles_a_head_24_of_them_masked():
    """L 8,192, B 4, 1,024 x 1,024 tiles: 8 (noisy x noisy, diagonal
    only) + 36 (noisy x clean) + 36 (clean x clean); the clean x noisy
    quadrant and both upper triangles take no step."""
    mask = fa.BlockDiffusionMask(8192, 4)
    for key_major in (False, True):
        table = fa.tile_table(16, 16, 1024, 1024, mask, None,
                              key_major=key_major)
        assert np.bincount(table[:, 2], minlength=3).tolist() == [56, 24, 0]
    table = fa.tile_table(16, 16, 1024, 1024, mask, None)
    tiles = {(qi, ki): kind for qi, ki, kind in table.tolist()}
    for qi in range(8):                                  # the noisy rows
        assert tiles[qi, qi] == fa.DIAGONAL
        assert tiles[qi, 8 + qi] == fa.DIAGONAL
        assert all(tiles[qi, 8 + ki] == fa.INTERIOR for ki in range(qi))
        assert not any((qi, ki) in tiles for ki in range(8) if ki != qi)
    for qi in range(8, 16):                              # the clean rows
        assert not any((qi, ki) in tiles for ki in range(8))
        assert tiles[qi, qi] == fa.DIAGONAL


CAUSAL_TABLES = {
    "8192_at_512": (8192, 8192, 512, 512), "square": (512, 512, 128, 128),
    "more_keys_than_queries": (256, 512, 128, 128),
    "fewer_keys_than_queries": (320, 128, 128, 128),
    "ragged": (200, 200, 128, 128), "unequal_blocks": (512, 512, 256, 128),
    "ragged_keys_only": (256, 300, 128, 64),
}


@pytest.mark.parametrize("case", list(CAUSAL_TABLES))
def test_causal_table_is_the_dense_masks_classification(case):
    sq, sk, bq, bk = CAUSAL_TABLES[case]
    nq, nk = -(-sq // bq), -(-sk // bk)
    kv_len = sk if sk % bk else None
    want = brute_force_kinds(dense_causal(sq, sk, nq * bq, nk * bk), bq, bk,
                             kv_len)
    mask = fa.static_mask(True, None, sq, sk)
    assert mask == fa.CausalMask(sk - sq)
    for key_major in (False, True):
        rows = fa.tile_table(nq, nk, bq, bk, mask, kv_len,
                             key_major=key_major).tolist()
        for qi, ki, kind in rows:
            assert want[qi, ki] == kind
        assert {(r[0], r[1]) for r in rows if r[2] != fa.DEAD} \
            == {t for t, kind in want.items() if kind != fa.DEAD}


#: tables of the PARENT commit (097d680, before masks were objects), row
#: for row: (nq, nk, block_q, block_k, causal, offset, kv_len) -> rows
#: query-major, rows key-major
FROZEN = {
    "square_4x4": ((4, 4, 128, 128, True, 0, None), [
        [0, 0, 1], [1, 0, 0], [1, 1, 1], [2, 0, 0], [2, 1, 0], [2, 2, 1],
        [3, 0, 0], [3, 1, 0], [3, 2, 0], [3, 3, 1]], [
        [0, 0, 1], [1, 0, 0], [2, 0, 0], [3, 0, 0], [1, 1, 1], [2, 1, 0],
        [3, 1, 0], [2, 2, 1], [3, 2, 0], [3, 3, 1]]),
    "more_keys": ((2, 4, 128, 128, True, 256, None), [
        [0, 0, 0], [0, 1, 0], [0, 2, 1], [1, 0, 0], [1, 1, 0], [1, 2, 0],
        [1, 3, 1]], [
        [0, 0, 0], [1, 0, 0], [0, 1, 0], [1, 1, 0], [0, 2, 1], [1, 2, 0],
        [1, 3, 1]]),
    "fewer_keys": ((3, 1, 128, 128, True, -192, None),
                   [[0, 0, 2], [1, 0, 1], [2, 0, 1]],
                   [[1, 0, 1], [2, 0, 1]]),
    "ragged": ((2, 2, 128, 128, True, 0, 200),
               [[0, 0, 1], [1, 0, 0], [1, 1, 1]],
               [[0, 0, 1], [1, 0, 0], [1, 1, 1]]),
    "unequal_blocks": ((2, 4, 256, 128, True, 0, None), [
        [0, 0, 1], [0, 1, 1], [1, 0, 0], [1, 1, 0], [1, 2, 1], [1, 3, 1]], [
        [0, 0, 1], [1, 0, 0], [0, 1, 1], [1, 1, 0], [1, 2, 1], [1, 3, 1]]),
    "not_causal_ragged": ((2, 3, 128, 128, False, 44, 300), [
        [0, 0, 0], [0, 1, 0], [0, 2, 1], [1, 0, 0], [1, 1, 0], [1, 2, 1]], [
        [0, 0, 0], [1, 0, 0], [0, 1, 0], [1, 1, 0], [0, 2, 1], [1, 2, 1]]),
}
#: and two of the decoder cell's size by the sha256 of their bytes
FROZEN_DIGESTS = {
    (8, 8, 1024, 1024): ("04d9621e0a218bfc", "1b8b235408e6b941"),
    (64, 64, 128, 128): ("4ea9fc4f818e0ebf", "b3dc9febdf0764e8"),
}


@pytest.mark.parametrize("case", list(FROZEN))
def test_causal_tables_are_the_parents_row_for_row(case):
    (nq, nk, bq, bk, causal, off, kv_len), by_query, by_key = FROZEN[case]
    mask = fa.CausalMask(off) if causal else None
    assert fa.tile_table(nq, nk, bq, bk, mask, kv_len).tolist() == by_query
    assert fa.tile_table(nq, nk, bq, bk, mask, kv_len,
                         key_major=True).tolist() == by_key
    # dk/dv at one head a group: the parent's table, not a fourth column
    assert fa.tile_table(nq, nk, bq, bk, mask, kv_len, key_major=True,
                         groups=1).tolist() == by_key
    # and with a group, each head's rows of a key block are the parent's
    by_head = fa.tile_table(nq, nk, bq, bk, mask, kv_len, key_major=True,
                            groups=2).tolist()
    live = [r for r in by_key if r[2] != fa.DEAD]
    for head in range(2):
        assert [r[:3] for r in by_head
                if r[3] == head and r[2] != fa.DEAD] == live


def test_the_decoder_cells_causal_tables_are_the_parents_bytes():
    for (nq, nk, bq, bk), digests in FROZEN_DIGESTS.items():
        for key_major, want in zip((False, True), digests):
            table = fa.tile_table(nq, nk, bq, bk, fa.CausalMask(0), None,
                                  key_major=key_major)
            assert table.dtype == np.int32
            assert hashlib.sha256(table.tobytes()).hexdigest()[:16] == want
        # the dk/dv launch at one head a group prefetches these bytes
        one = fa.tile_table(nq, nk, bq, bk, fa.CausalMask(0), None,
                            key_major=True, groups=1)
        assert hashlib.sha256(one.tobytes()).hexdigest()[:16] == digests[1]
        # lfm2's 4 and sdar's 8 heads a group visit the same tiles, each
        # key block over the group's heads in turn
        for groups in (4, 8):
            grouped = fa.tile_table(nq, nk, bq, bk, fa.CausalMask(0), None,
                                    key_major=True, groups=groups)
            assert grouped.shape == (len(one) * groups, 4)
            assert grouped[grouped[:, 3] == groups - 1][:, :3].tobytes() \
                == one.tobytes()


def test_a_mask_is_given_in_place_of_causal_and_over_its_own_rows():
    mask = fa.BlockDiffusionMask(16, 4)
    assert fa.static_mask(False, mask, 32, 32) is mask
    assert fa.static_mask(False, None, 8, 8) is None
    with pytest.raises(ValueError, match="in place of"):
        fa.static_mask(True, mask, 32, 32)
    with pytest.raises(ValueError, match="32 rows"):
        fa.static_mask(False, mask, 16, 16)
    with pytest.raises(ValueError, match="TileMask"):
        fa.static_mask(False, np.ones((4, 4), bool), 4, 4)
    with pytest.raises(ValueError, match="multiple"):
        fa.BlockDiffusionMask(18, 4)
    assert hash(mask) == hash(fa.BlockDiffusionMask(16, 4))


# --------------------------------- (b) the kernels under the mask

def _dense_attention(q, k, v, allowed):
    return attention_lib._reference_attention(q, k, v, mask=allowed)


#: id -> (L, B, block_q, block_k, noisy, kinds of step the table holds)
KERNEL_CASES = {
    "aligned_4x4_tiles": (256, 4, 128, 128, True, {0, 1}),
    "ragged_rows": (200, 8, 128, 128, True, {0, 1}),
    "unequal_blocks": (512, 16, 256, 128, True, {0, 1}),
    "clean_half_alone": (256, 4, 128, 128, False, {0, 1}),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_kernels_under_the_mask_match_dense_masked_attention(monkeypatch,
                                                             case):
    """The real kernel bodies, interpreted, over the table of live tiles:
    output, ``dq``, ``dk``, ``dv`` against dense attention under the same
    mask and its vjp (float32; 2e-5: the online softmax's order of
    sums)."""
    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    L, B, bq, bk, noisy, kinds = KERNEL_CASES[case]
    mask = fa.BlockDiffusionMask(L, B, noisy)
    n = mask.rows
    _, _, _, bq_p, bk_p, n_q, n_k, _ = fa._pad_blocks(
        *(jnp.zeros((1, n, 1, 64)),) * 3, bq, bk)
    table = fa.tile_table(n_q // bq_p, n_k // bk_p, bq_p, bk_p, mask,
                          n if n_k != n else None)
    assert set(table[:, 2].tolist()) == kinds
    assert len(table) < (n_q // bq_p) * (n_k // bk_p)    # dead tiles left
    rng = np.random.default_rng(L + B)
    q, k, v, g = (jnp.asarray(rng.normal(size=(1, n, 2, 64)), jnp.float32)
                  for _ in range(4))
    allowed = jnp.asarray(dense_block_diffusion(L, B, n, noisy))

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, False, bq, bk, mask)

    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(
        lambda q, k, v: _dense_attention(q, k, v, allowed), q, k, v)
    assert rel(out, want) < 2e-5
    for got_g, want_g, name in zip(vjp(g), want_vjp(g), ("dq", "dk", "dv")):
        assert rel(got_g, want_g) < 2e-5, name
    # the scan the CPU falls back to takes the same mask
    scan = fa.blockwise_attention(q, k, v, block_k=bk, mask=mask)
    assert rel(scan, want) < 2e-5


def test_dispatch_hands_a_static_mask_to_the_kernels_and_an_array_to_dense(
        monkeypatch):
    from analytics_zoo_tpu.ops import autotune
    mask = fa.BlockDiffusionMask(8, 4)
    q = jnp.asarray(np.random.default_rng(0).normal(size=(1, 16, 2, 8)),
                    jnp.float32)
    dense = attention_lib.dot_product_attention(q, q, q, mask=mask)
    by_array = attention_lib.dot_product_attention(
        q, q, q, mask=jnp.asarray(dense_block_diffusion(8, 4, 16)))
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(by_array))
    assert not attention_lib._flash_ok(q, q, mask)           # on the CPU
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    monkeypatch.setattr(autotune, "on_tpu", lambda: True)
    monkeypatch.setattr(autotune, "SCORES_SWITCH", 0)
    assert attention_lib._flash_ok(q, q, mask)
    assert not attention_lib._flash_ok(q, q, mask.dense(16, 16))
    calls = []
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda q, k, v, causal, bq, bk, mask: calls.append(
            (causal, bq, bk, mask)) or q)
    attention_lib.dot_product_attention(q, q, q, mask=mask)
    assert calls == [(False,) + autotune.UNTUNED_BLOCKS + (mask,)]


# ------------------------- (c) the module against the reference

def _module_and_params(seed, **over):
    cfg = tiny_cfg(**over)
    return cfg, model_lib.build_module(cfg), ref.make_params(cfg, seed)


def test_parameter_tree_is_the_references_layout():
    cfg = tiny_cfg()
    x, _ = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(0), 2)
    variables = jax.eval_shape(
        lambda: model_lib.build_module(cfg).init(jax.random.PRNGKey(0), x))
    got = {k: v.shape for k, v in leaves(variables["params"]).items()}
    want = leaves(jax.tree_util.tree_map(
        lambda s: np.zeros(s), ref.param_shapes(cfg),
        is_leaf=lambda s: isinstance(s, tuple)))
    assert got == {k: v.shape for k, v in want.items()}
    assert not any("expert_bias" in k for k in got)          # softmax router
    assert got["decoder/lm_head/kernel"] == (32, 96)         # untied


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 7])
def test_corruption_is_the_references_for_the_same_key(seed):
    """``xt``, ``m``, ``t`` as the module draws them from the Estimator's
    step key equal the reference's, bit for bit (the reference restates
    flax's key folding and the order of the two draws)."""
    cfg, module, params = _module_and_params(seed)
    x, _ = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(seed), 4)
    key = program.step_key(3)
    seen = {}
    real = block_diffusion.corrupt

    def spy(ids, key, block, mask_id, eps):
        seen["out"] = real(ids, key, block, mask_id, eps)
        return seen["out"]

    block_diffusion.corrupt, _ = spy, None
    try:
        module.apply({"params": params}, x, train=True,
                     rngs={"dropout": key}, mutable=["counters"])
    finally:
        block_diffusion.corrupt = real
    want = ref.corrupt(jnp.asarray(x), key, cfg)
    for got, w in zip(seen["out"], want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(w))
    xt, m, t = (np.asarray(a) for a in want)
    assert t.shape == (4, 4) and (t >= 1e-3).all() and (t < 1).all()
    assert ((xt == cfg["mask_token_id"]) == m).all()
    assert (xt[~m] == x[~m]).all() and 0 < m.mean() < 1


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 7])
def test_logits_weights_and_loss_agree_with_the_reference(seed):
    cfg, module, params = _module_and_params(seed)
    x, y = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(seed), 4)
    key = program.step_key(0)
    (logits, weights), mut = module.apply(
        {"params": params}, x, train=True, rngs={"dropout": key},
        mutable=["counters"])
    xt, m, t = ref.corrupt(jnp.asarray(x), key, cfg)
    want = ref.forward(params, xt, jnp.asarray(x), cfg)
    assert logits.shape == (4, 16, cfg["vocab_size"])
    # float32 on both sides, different orders of sums: 1e-5
    assert rel(logits, want) < 1e-5
    want_w = np.asarray(m) / np.repeat(np.asarray(t), 4, axis=1)
    np.testing.assert_allclose(np.asarray(weights), want_w, rtol=1e-6)
    loss = losses.get(model_lib.LOSS)(y, (logits, weights)).mean()
    assert float(loss) == pytest.approx(float(ref.loss_sum(
        params, xt, jnp.asarray(x), jnp.asarray(want_w), cfg)) / y.size,
        rel=1e-5)
    counted = {k: int(v) for k, v in leaves(mut["counters"]).items()
               if "diffusion" in k}
    assert counted == {
        "zoo_diffusion_positions_total{masked=true}": int(m.sum()),
        "zoo_diffusion_positions_total{masked=false}": int((~m).sum())}


def test_every_leafs_gradient_and_three_adam_steps_through_fit(orca_ctx):
    """Three optimizer steps of ``fit`` (the benchmark's own build and
    feed): each step's loss is the reference's, Adam's first moment after
    the first step over 1 - b1 is the reference's gradient leaf by leaf,
    and the parameters after the third are those ``references/adam.py``
    reaches. 2e-4 a leaf: float32 on both sides, the program's sums in
    another order (the router's leaf, whose gradient is a difference of
    near-equal terms, reads 1e-4)."""
    cfg = tiny_cfg()
    seed, batch, lr = 11, 8, 1e-5
    params = ref.make_params(cfg, seed)
    x, y = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(seed), 3 * batch)
    est = program.build_estimator(
        model_lib.build_module(cfg), model_lib.LOSS,
        {"name": "adam", "learningrate": lr}, params, x[:2])
    loss_and_grad = ref.make_loss_and_grad(cfg, batch, 4)
    want_params, state = params, adam.init(params)
    for s in range(3):
        rows = slice(s * batch, (s + 1) * batch)
        hist = est.fit((x[rows], y[rows]), epochs=1, batch_size=batch,
                       shuffle=False)
        loss, grads = loss_and_grad(want_params, x[rows], y[rows],
                                    program.step_key(s))
        assert hist["loss"][-1] == pytest.approx(loss, rel=1e-5)
        if s == 0:
            got = jax.tree_util.tree_map(lambda m: np.asarray(m) / 0.1,
                                         program.first_moment(est))
            got_leaves, want_leaves = leaves(got), leaves(grads)
            assert set(got_leaves) == set(want_leaves)
            for name, w in want_leaves.items():
                assert np.any(w), name
                assert rel(got_leaves[name], w) < 2e-4, name
        want_params, state = adam.step(want_params, grads, state, lr=lr)
    moved = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - b,
                                   program.parameters(est), params)
    want_moved = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - b,
                                        want_params, params)
    for name, w in leaves(want_moved).items():
        # three steps of at most the rate each: a leaf's change is known
        # to a few per cent of itself where gradients are near zero
        assert rel(leaves(moved)[name], w) < 0.05, name
    grown = telemetry.snapshot()["zoo_diffusion_positions_total"]
    assert sum(grown.values()) == 3 * batch * 16


def test_predict_and_evaluate_get_one_array(orca_ctx):
    """Outside training the module returns the clean text's logits under
    the mask's clean part: one array, which the loss takes unweighted."""
    cfg, module, params = _module_and_params(3)
    x, y = model_lib.make_inputs(cfg, {"seq_len": 16},
                                 np.random.default_rng(3), 8)
    est = program.build_estimator(module, model_lib.LOSS, "adam", params,
                                  x[:2])
    preds = np.asarray(est.predict(x, batch_size=8))
    assert preds.shape == (8, 16, cfg["vocab_size"])
    clean = module.apply({"params": params}, x)
    np.testing.assert_allclose(preds, np.asarray(clean), rtol=1e-5,
                               atol=1e-6)
    assert np.isfinite(est.evaluate((x, y), batch_size=8)["loss"])
    # they are the clean half's of the training layout with nothing masked
    both = block_diffusion.HybridDecoder(module.config).apply(
        {"params": params["decoder"]}, np.concatenate([x, x], 1),
        positions=np.tile(np.arange(16), 2),
        mask=fa.BlockDiffusionMask(16, 4), head_rows=(16, 32))
    assert rel(clean, both) < 1e-5


# ----------------------------------------------------- (d) what leaks

def _halves(module, params, xt, x0):
    """Logits of the noisy and of the clean half of ``[xt ; x0]``."""
    L = x0.shape[1]
    decoder = block_diffusion.HybridDecoder(module.config)
    logits = decoder.apply(
        {"params": params["decoder"]}, np.concatenate([xt, x0], 1),
        positions=np.tile(np.arange(L), 2),
        mask=fa.BlockDiffusionMask(L, module.block))
    return np.asarray(logits[:, :L]), np.asarray(logits[:, L:])


@pytest.mark.parametrize("b", [0, 2, 3])
def test_nothing_leaks_across_the_mask(b):
    """Changing a clean id of block ``b`` moves no noisy-half logit of
    blocks ``<= b`` and no clean-half logit of blocks ``< b``; changing a
    noisy id of block ``b`` moves noisy logits of block ``b`` only.
    Exactly: what a row may not see enters none of its sums."""
    cfg, module, params = _module_and_params(5)
    B = cfg["block_length"]
    rng = np.random.default_rng(b)
    x0 = rng.integers(0, cfg["mask_token_id"], (2, 16), dtype=np.int32)
    xt = np.where(rng.random((2, 16)) < 0.5, cfg["mask_token_id"], x0) \
        .astype(np.int32)
    noisy, clean = _halves(module, params, xt, x0)
    at = b * B + 1
    blocks = np.arange(16) // B

    x0_changed = x0.copy()
    x0_changed[:, at] = (x0[:, at] + 1) % cfg["mask_token_id"]
    noisy_c, clean_c = _halves(module, params, xt, x0_changed)
    np.testing.assert_array_equal(noisy_c[:, blocks <= b],
                                  noisy[:, blocks <= b])
    np.testing.assert_array_equal(clean_c[:, blocks < b],
                                  clean[:, blocks < b])
    assert not np.array_equal(clean_c[:, blocks == b], clean[:, blocks == b])
    if b < 3:
        assert not np.array_equal(noisy_c[:, blocks > b],
                                  noisy[:, blocks > b])

    xt_changed = xt.copy()
    xt_changed[:, at] = (xt[:, at] + 1) % cfg["mask_token_id"]
    noisy_n, clean_n = _halves(module, params, xt_changed, x0)
    np.testing.assert_array_equal(clean_n, clean)
    np.testing.assert_array_equal(noisy_n[:, blocks != b],
                                  noisy[:, blocks != b])
    assert not np.array_equal(noisy_n[:, blocks == b], noisy[:, blocks == b])


# ---------------------------------------- (e) the softmax router

def test_softmax_routing_is_the_published_rule():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    ids, weights = moe_lib.softmax_top_k_routing(logits, 3)
    probs = np.asarray(jax.nn.softmax(logits, -1))
    order = np.argsort(-probs, -1)[:, :3]
    np.testing.assert_array_equal(np.sort(np.asarray(ids), -1),
                                  np.sort(order, -1))
    picked = np.take_along_axis(probs, np.asarray(ids), -1)
    np.testing.assert_allclose(np.asarray(weights),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    _, raw = moe_lib.softmax_top_k_routing(logits, 3, normalize=False)
    np.testing.assert_allclose(np.asarray(raw), picked, rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        moe_lib.Router(8, 2, scoring="tanh").init(
            jax.random.PRNGKey(0), jnp.zeros((4, 8)))


def test_the_8_shares_of_16_experts_add_up_to_the_uncut_layer():
    """One layer, 128 experts in 8 shares of 16 under the softmax router:
    the shares' partial results add up to the layer that holds them all,
    in the program and in the reference (1e-5: float32 sums in another
    order)."""
    hidden, n_experts, k, width = 32, 128, 8, 24
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(2, 16, hidden)), jnp.float32)

    def layer(held):
        return moe_lib.DroplessMoE(n_experts, k, width, held,
                                   scoring="softmax")

    whole = layer(None)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    assert set(params["router"]) == {"kernel"}
    want = whole.apply({"params": params}, x)
    total = 0
    for share in range(8):
        held = tuple(range(16 * share, 16 * share + 16))
        mine = dict(params, experts={
            name: w[16 * share:16 * share + 16]
            for name, w in params["experts"].items()})
        part, mut = layer(held).apply({"params": mine}, x,
                                      mutable=["counters"])
        total = total + part
    assert rel(total, want) < 1e-5
    # and the reference's share is the program's
    cfg = tiny_cfg(hidden_size=hidden, router_experts=n_experts,
                   num_experts_per_tok=k, moe_intermediate_size=width,
                   held_experts=list(range(16)), num_experts=16)
    mine = {"router": params["router"], "experts": {
        name: w[:16] for name, w in params["experts"].items()}}
    got = layer(tuple(range(16))).apply({"params": mine}, x,
                                        mutable=["counters"])[0]
    assert rel(got, ref._moe(mine, x.reshape(-1, hidden), cfg)
               .reshape(x.shape)) < 1e-5


# ------------------------------------------------- the weighted loss

def test_weighted_loss_weighs_each_position_and_takes_bare_logits(
        monkeypatch):
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(3, 8, 11)), jnp.float32)
    labels = rng.integers(0, 11, (3, 8))
    weights = jnp.asarray(rng.random((3, 8)) * (rng.random((3, 8)) < 0.5),
                          jnp.float32)
    fn = losses.get("weighted_sparse_categorical_crossentropy_logits")
    nll = -np.take_along_axis(np.asarray(jax.nn.log_softmax(logits)),
                              labels[..., None], -1)[..., 0]
    np.testing.assert_allclose(np.asarray(fn(labels, (logits, weights))),
                               (nll * np.asarray(weights)).mean(-1),
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(fn(labels, logits)),
        np.asarray(losses.sparse_categorical_crossentropy_from_logits(
            labels, logits)), rtol=1e-6)
    # in blocks of positions as whole: the same loss, the same gradient
    whole = jax.value_and_grad(
        lambda z: fn(labels, (z, weights)).mean())(logits)
    monkeypatch.setattr(losses, "LOGITS_BLOCK_BYTES", 8 * 11 * 4)
    blocked = jax.value_and_grad(
        lambda z: fn(labels, (z, weights)).mean())(logits)
    assert "while" in jax.jit(lambda z: fn(labels, (z, weights))) \
        .lower(logits).as_text()
    np.testing.assert_allclose(np.asarray(blocked[0]), np.asarray(whole[0]),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(blocked[1]), np.asarray(whole[1]),
                               rtol=1e-5, atol=1e-8)


def test_rotary_positions_may_repeat():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 8, 2, 16)),
                    jnp.float32)
    twice = attention_lib.rotary_embedding(
        jnp.concatenate([x, x], 1), 1e6, np.tile(np.arange(8), 2))
    once = attention_lib.rotary_embedding(x, 1e6)
    np.testing.assert_array_equal(np.asarray(twice[:, :8]), np.asarray(once))
    np.testing.assert_array_equal(np.asarray(twice[:, 8:]), np.asarray(once))
