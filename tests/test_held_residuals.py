"""The training step's held residuals (``analytics_zoo_tpu/ops/hold.py``):
the exact gelu's erfc and every dropout mask sit behind an optimization
barrier in a differentiated program, and nowhere else; not one bit of a
value, a gradient or a mask changes."""

import contextlib
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import attention as attention_lib
from analytics_zoo_tpu.ops import hold
from analytics_zoo_tpu.text import bert as bert_lib

BARRIER = re.compile(r"\bstablehlo\.optimization_barrier\b")


def barriers(lowered) -> int:
    return len(BARRIER.findall(lowered.as_text()))


def assert_bitwise(got, want):
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for a, b in zip(got_leaves, want_leaves):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)
        assert a.tobytes() == b.tobytes()       # signs of zero, NaN bits


# ------------------------------------------------------------------ gelu

@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_gelu_exact_is_jax_gelu_bit_for_bit(dtype, jitted):
    x = (jax.random.normal(jax.random.PRNGKey(0), (64, 96)) * 3).astype(dtype)
    g = jax.random.normal(jax.random.PRNGKey(1), x.shape).astype(dtype)

    def both(fn):
        def run(x, g):
            y, vjp = jax.vjp(fn, x)
            return y, vjp(g)[0], fn(x)      # differentiated and plain
        return jax.jit(run) if jitted else run

    got = both(hold.gelu_exact)(x, g)
    want = both(lambda x: jax.nn.gelu(x, approximate=False))(x, g)
    assert got[0].dtype == dtype
    assert_bitwise(got, want)


def test_gelu_exact_promotes_integers_as_jax_gelu_does():
    x = jnp.arange(-3, 4)
    assert_bitwise(hold.gelu_exact(x), jax.nn.gelu(x, approximate=False))


def test_hold_is_a_barrier_under_grad_and_nothing_otherwise():
    x = jnp.linspace(-2.0, 2.0, 32)
    plain = jax.jit(lambda x: hold.hold(jnp.sin(x)) * 2).lower(x)
    assert barriers(plain) == 0
    assert "optimization_barrier" not in plain.as_text()
    # the square keeps the held value as its residual
    under_grad = jax.jit(jax.grad(
        lambda x: (hold.hold(jnp.sin(x)) ** 2).sum())).lower(x)
    assert barriers(under_grad) == 1
    np.testing.assert_array_equal(
        jax.grad(lambda x: (hold.hold(jnp.sin(x)) ** 2).sum())(x),
        jax.grad(lambda x: (jnp.sin(x) ** 2).sum())(x))


# --------------------------------------------------------------- dropout

class _Site(nn.Module):
    """One dropout at module path ``bert/block_0/attention/Dropout_0``,
    flax's or the held one."""
    cls: type

    @nn.compact
    def __call__(self, x):
        class attention(nn.Module):
            @nn.compact
            def __call__(inner, x):
                return self.cls(0.1, deterministic=False)(x)

        class block(nn.Module):
            @nn.compact
            def __call__(inner, x):
                return attention(name="attention")(x)

        class bert(nn.Module):
            @nn.compact
            def __call__(inner, x):
                return block(name="block_0")(x)

        return bert(name="bert")(x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_held_dropout_draws_flaxs_mask_at_the_same_path(dtype):
    from benchmarks.references import bert as reference
    key = jax.random.PRNGKey(17)
    x = (jax.random.normal(jax.random.PRNGKey(2), (8, 16, 32)) + 3) \
        .astype(dtype)
    g = jnp.ones_like(x)

    def run(cls):
        def fn(x):
            return _Site(cls).apply({}, x, rngs={"dropout": key})
        y, vjp = jax.vjp(fn, x)
        return y, vjp(g)[0]

    got, want = run(hold.Dropout), run(nn.Dropout)
    assert_bitwise(got, want)
    # and the mask the benchmark's reference restates for that site
    site = ("bert", "block_0", "attention", "Dropout_0")
    mask = jax.random.bernoulli(
        reference.fold_static(key, site + (1,)), 0.9, x.shape)
    np.testing.assert_array_equal(np.asarray(got[0] != 0), np.asarray(mask))
    assert 0.8 < float(mask.mean()) < 0.97


@pytest.mark.parametrize("rate,deterministic,barriers_wanted", [
    (0.1, False, 1), (0.1, True, 0), (0.0, False, 0), (1.0, False, 0)])
def test_held_dropout_edges_follow_flax(rate, deterministic, barriers_wanted):
    x = jnp.ones((4, 8))
    key = jax.random.PRNGKey(3)

    def fn(cls):
        return lambda x: cls(rate, deterministic=deterministic).apply(
            {}, x, rngs={"dropout": key})

    assert_bitwise(fn(hold.Dropout)(x), fn(nn.Dropout)(x))
    assert barriers(jax.jit(fn(hold.Dropout)).lower(x)) == barriers_wanted


# ------------------------------------------------- the whole train step

N_BLOCK = 2


def _bert(name=None, **cfg):
    cfg = dict(dict(vocab=50, hidden_size=32, n_block=N_BLOCK, n_head=4,
                    intermediate_size=64, max_position_len=16), **cfg)
    return bert_lib.BertModule(bert_lib.BertConfig(**cfg), name=name)


def _gpt(**cfg):
    return bert_lib.TransformerModule(
        vocab=50, hidden_size=32, n_block=N_BLOCK, n_head=4,
        max_position_len=16, **cfg)


def _loss_and_grads(module, ids, train=True, key=jax.random.PRNGKey(17)):
    params = module.init(jax.random.PRNGKey(0), ids)

    def loss(params):
        out = module.apply(params, ids, train=train, rngs={"dropout": key})
        out = out[1] if isinstance(out, tuple) else out
        return (out.astype(jnp.float32) ** 2).mean()

    return jax.jit(jax.value_and_grad(loss)), params


@pytest.fixture
def unheld(monkeypatch):
    """The same modules built with ``nn.gelu`` and ``nn.Dropout``: the
    step as it was before the residuals were held."""
    def apply():
        monkeypatch.setattr(bert_lib, "Dropout", nn.Dropout)
        monkeypatch.setattr(attention_lib, "Dropout", nn.Dropout)
        monkeypatch.setattr(bert_lib, "gelu_exact",
                            lambda h: nn.gelu(h, approximate=False))
    return apply


IDS = np.random.default_rng(0).integers(0, 50, (4, 16)).astype(np.int32)


@pytest.mark.parametrize("build,dtype", [
    (_bert, None), (_bert, jnp.bfloat16), (_gpt, None), (_gpt, jnp.bfloat16)],
    ids=["bert-fp32", "bert-bf16", "gpt-fp32", "gpt-bf16"])
def test_train_step_is_bitwise_the_unheld_step(build, dtype, unheld):
    step, params = _loss_and_grads(build(dtype=dtype), IDS)
    got = step(params)
    held = barriers(step.lower(params))
    unheld()
    step, params = _loss_and_grads(build(dtype=dtype), IDS)
    assert barriers(step.lower(params)) == 0 < held
    want = step(params)
    assert np.isfinite(float(want[0]))
    assert_bitwise(got, want)


@pytest.mark.parametrize("build,gelus", [(_bert, N_BLOCK), (_gpt, 0)],
                         ids=["bert", "gpt-tanh-gelu"])
def test_lowered_train_step_holds_one_value_per_site(build, gelus):
    """One barrier per exact gelu and per active dropout site (one after
    the embeddings, two a block); none where dropout is off, none in a
    forward pass that is not differentiated."""
    module = build()
    step, params = _loss_and_grads(module, IDS)
    assert barriers(step.lower(params)) == gelus + 1 + 2 * N_BLOCK

    step, params = _loss_and_grads(build(hidden_drop=0.0, attn_drop=0.0), IDS)
    assert barriers(step.lower(params)) == gelus

    forward = jax.jit(lambda p, ids: module.apply(p, ids, train=False))
    assert "optimization_barrier" not in forward.lower(params, IDS).as_text()
    # evaluate-style: not differentiated, dropout off
    step, params = _loss_and_grads(module, IDS, train=False)
    assert barriers(step.lower(params)) == gelus


def test_inference_model_program_holds_nothing(orca_ctx):
    from analytics_zoo_tpu.common import profiling, telemetry
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    im = InferenceModel().load_flax(_bert(), IDS)
    im.warm_up(rungs=(4,), block=True)
    assert np.asarray(im.predict(IDS)[1]).shape == (4, 32)
    counts = profiling.step_counts("inference_model")
    assert counts["held_values"] == 0 and counts["mask"] == 0
    snap = telemetry.snapshot()
    assert snap["zoo_step_held_values"]["executable=inference_model"] == 0
    assert snap["zoo_step_elementwise_evals"][
        "executable=inference_model,kind=mask"] == 0


def test_remat_holds_the_values_inside_the_checkpointed_block(unheld):
    """The held values are recomputed once in the backward pass, by
    design: each site of a block shows twice in the lowered step (beside
    the one barrier ``jax.checkpoint`` itself puts around each block's
    recomputation), and the numbers are those of the step that keeps
    everything."""
    step, params = _loss_and_grads(_bert(), IDS)
    want = step(params)
    remat, params_r = _loss_and_grads(_bert(remat=True), IDS)
    assert_bitwise(params_r, params)
    in_blocks = N_BLOCK + 2 * N_BLOCK
    assert barriers(remat.lower(params)) == N_BLOCK + 1 + 2 * in_blocks
    got = remat(params)
    unheld()
    remat, _ = _loss_and_grads(_bert(remat=True), IDS)
    assert barriers(remat.lower(params)) == N_BLOCK
    assert_bitwise(got, remat(params))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def _fit_tiny_bert(steps_per_loop):
    from analytics_zoo_tpu.learn.estimator import Estimator

    class Classifier(nn.Module):
        @nn.compact
        def __call__(self, ids, train: bool = False):
            _, pooled = _bert(name="bert")(ids, train=train)
            return nn.Dense(2)(pooled)

    rng = np.random.default_rng(1)
    x = rng.integers(0, 50, (32, 16)).astype(np.int32)
    y = rng.integers(0, 2, 32).astype(np.int32)
    est = Estimator.from_flax(
        model=Classifier(), loss="sparse_categorical_crossentropy_logits",
        optimizer="sgd", sample_input=x[:2], seed=0)
    hist = est.fit((x, y), epochs=1, batch_size=8, shuffle=False,
                   steps_per_loop=steps_per_loop)
    est._precompile_thread.join(timeout=300)
    return est, hist


def test_steps_per_loop_runs_the_same_held_step_inside_a_scan(orca_ctx):
    from analytics_zoo_tpu.common import profiling
    est1, h1 = _fit_tiny_bert(1)
    est2, h2 = _fit_tiny_bert(2)
    np.testing.assert_allclose(h1["loss"], h2["loss"], rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(est1._state["params"]),
                    jax.tree_util.tree_leaves(est2._state["params"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    sites = N_BLOCK + 1 + 2 * N_BLOCK
    assert profiling.step_counts("estimator_train_step")["held_values"] \
        == sites
    assert profiling.step_counts("estimator_train_scan")["held_values"] \
        == sites


# ------------------------------ the v5e compiler, described not attached

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def compiled_outside_the_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def test_v5e_compiles_each_held_value_once_at_bert_base_width(one_chip):
    """One block of BERT-base at [32,512], bf16, compiled for the chip:
    the step's optimized HLO evaluates erfc once and generates one mask
    per dropout site. (Without the barriers the same compile reads 3
    and 10: each re-derived inside the products that read it.)"""
    from analytics_zoo_tpu.common import profiling
    module = _bert(vocab=30522, hidden_size=768, n_block=1, n_head=12,
                   intermediate_size=3072, max_position_len=512,
                   dtype=jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((32, 512), jnp.int32, sharding=one_chip)
    params = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0),
                            jnp.zeros((2, 512), jnp.int32)))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)

    def step(params, ids):
        def loss(params):
            _, pooled = module.apply(
                params, ids, train=True,
                rngs={"dropout": jax.random.PRNGKey(17)})
            return (pooled.astype(jnp.float32) ** 2).mean()
        loss_val, grads = jax.value_and_grad(loss)(params)
        return loss_val, jax.tree_util.tree_map(
            lambda p, g: p - 1e-3 * g, params, grads)

    with compiled_outside_the_cache():
        lowered = jax.jit(step, donate_argnums=0).lower(params, ids)
        text = lowered.compile().as_text()
    assert barriers(lowered) == 1 + 3
    assert profiling.count_elementwise_evals(text) == {"erfc": 1, "mask": 3}


@pytest.mark.parametrize("policy,forward_launches", [
    ("no checkpoint", 1), ("products alone", 2), ("the decoder's", 1)],
    ids=["no_checkpoint", "products_alone", "the_decoders_policy"])
def test_v5e_launches_the_forward_kernel_once_where_its_residuals_are_kept(
        one_chip, policy, forward_launches):
    """``tanh(x @ w) -> flash_attention(causal) -> @ w`` at the decoder's
    head size and untuned blocks, compiled for the chip: the compiler
    drops the recomputation's launch of the forward kernel when the
    checkpoint's policy keeps ``flash_attention.RESIDUAL_NAMES``, and
    does not merge the two launches when it keeps products alone."""
    from analytics_zoo_tpu.common import profiling
    from analytics_zoo_tpu.ops.autotune import UNTUNED_BLOCKS
    from analytics_zoo_tpu.ops.flash_attention import flash_attention
    from analytics_zoo_tpu.text import hybrid_decoder

    def layer(x, w):
        y = jnp.tanh(x @ w)
        return flash_attention(y, y, y, True, *UNTUNED_BLOCKS) @ w

    if policy != "no checkpoint":
        layer = jax.checkpoint(layer, policy={
            "products alone": hybrid_decoder._products_saveable,
            "the decoder's": hybrid_decoder._BLOCK_POLICY}[policy])
    x = jax.ShapeDtypeStruct((1, 2048, 2, 64), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((64, 64), jnp.bfloat16, sharding=one_chip)
    # the loss reads the layer's output, so the forward pass cannot be
    # dropped for the recomputation's sake
    grad = jax.jit(jax.grad(
        lambda x, w: (layer(x, w).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1)))
    with compiled_outside_the_cache():
        text = grad.lower(x, w).compile().as_text()
    assert profiling.count_kernel_calls(text) == {
        "flash_fwd": forward_launches, "flash_bwd_dq": 1,
        "flash_bwd_dkv": 1, "norm_rotary_fwd": 0, "norm_rotary_bwd": 0,
        "moe_pack_rows": 0, "moe_sum_rows": 0}
    # each launch lists its live tiles alone: two heads of one interior
    # and two diagonal tiles at 2,048 positions and 1,024 x 1,024 blocks
    steps = profiling.count_flash_grid_steps(text)
    assert steps == {
        f"{kernel}/{kind}": n * (forward_launches
                                 if kernel == "flash_fwd" else 1)
        for kernel in profiling.FLASH_KERNELS
        for kind, n in (("interior", 2), ("diagonal", 4), ("dead", 0))}


@pytest.mark.parametrize("sq,sk,causal,want", [
    (2048, 2048, True, (12, 8, 0)), (1024, 2048, True, (10, 4, 0)),
    (2048, 1024, True, (2, 4, 4)), (1024, 1500, False, (8, 4, 0)),
    (None, None, None, None)],
    ids=["causal", "more_keys_than_queries", "query_blocks_that_see_no_key",
         "not_causal_ragged_keys", "no_kernel"])
def test_v5e_flash_launches_take_no_step_for_a_dead_tile(one_chip, sq, sk,
                                                         causal, want):
    """Forward and backward compiled for the chip at the decoder's head
    size and 512 x 512 blocks: all three kernels build over the table of
    live tiles (scalar prefetch, data-dependent index maps) and say how
    many steps of each kind they take — none dead, but one for each query
    block that sees no key. A step without the kernels has no such
    count."""
    from analytics_zoo_tpu.common import profiling
    from analytics_zoo_tpu.ops.flash_attention import flash_attention

    if want is None:
        def layer(q, k):
            return jnp.tanh(q) * k.sum()
        sq = sk = 1024
    else:
        def layer(q, k):
            return flash_attention(q, k, k, causal, 512, 512)
    q = jax.ShapeDtypeStruct((1, sq, 2, 64), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, sk, 2, 64), jnp.bfloat16, sharding=one_chip)
    grad = jax.jit(jax.grad(
        lambda q, k: (layer(q, k).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1)))
    with compiled_outside_the_cache():
        text = grad.lower(q, k).compile().as_text()
    launched = want is not None
    assert profiling.count_kernel_calls(text) == {
        **dict.fromkeys(profiling.KERNEL_FUNCTIONS, 0),
        **dict.fromkeys(profiling.FLASH_KERNELS, int(launched))}
    # a kept dead step belongs to a query block: the key blocks' kernel,
    # every one of whose blocks some query sees, has none
    assert profiling.count_flash_grid_steps(text) == ({
        f"{kernel}/{kind}": n * (kernel != "flash_bwd_dkv" or kind != "dead")
        for kernel in profiling.FLASH_KERNELS
        for kind, n in zip(profiling.TILE_KINDS, want)} if launched else {})


def test_v5e_compiles_the_three_kernels_under_the_block_diffusion_mask(
        one_chip):
    """Forward and backward under ``BlockDiffusionMask`` compiled for the
    chip at the block-diffusion cell's head (128 wide, bfloat16) and its
    untuned 1,024 x 1,024 blocks, at 2 x 2,048 rows: the mask's predicate
    on a row and a column of positions builds in Mosaic, and each launch
    says it takes, a head, 2 + 3 + 3 = 8 live tiles of 16, 6 of them
    masked, none dead."""
    from analytics_zoo_tpu.common import profiling
    from analytics_zoo_tpu.ops import autotune
    from analytics_zoo_tpu.ops.flash_attention import (BlockDiffusionMask,
                                                       flash_attention)

    blocks = autotune.untuned_blocks(128, jnp.bfloat16)
    assert blocks == (1024, 1024)
    mask = BlockDiffusionMask(2048, 4)
    q = jax.ShapeDtypeStruct((1, 4096, 4, 128), jnp.bfloat16,
                             sharding=one_chip)
    grad = jax.jit(jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, False, *blocks, mask)
                         .astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1, 2)))
    with compiled_outside_the_cache():
        text = grad.lower(q, q, q).compile().as_text()
    assert profiling.count_kernel_calls(text) == {
        **dict.fromkeys(profiling.KERNEL_FUNCTIONS, 0),
        **dict.fromkeys(profiling.FLASH_KERNELS, 1)}
    assert profiling.count_flash_grid_steps(text) == {
        f"{kernel}/{kind}": n * 4
        for kernel in profiling.FLASH_KERNELS
        for kind, n in (("interior", 2), ("diagonal", 6), ("dead", 0))}


@pytest.mark.parametrize("shape,kv_heads,masking,layout,tiles,computed", [
    ((1, 16384, 32, 128), 4, "block_diffusion", "rows", (56, 24, 0),
     {128: 69206016, 256: 71303168}),
    ((2, 8192, 32, 64), 8, "causal", "heads", (28, 8, 0),
     {128: 34078720, 256: 34603008})],
    ids=["the_block_diffusion_cells_launches", "the_causal_cells_launches"])
def test_v5e_compiles_the_decoder_cells_grouped_launches(
        one_chip, monkeypatch, shape, kv_heads, masking, layout, tiles,
        computed):
    """The three launches at each decoder cell's own size — q at 32 heads,
    k and v at the cell's 4 or 8 — compiled for the chip at the untuned
    1,024 x 1,024 blocks: each fits the 16 MiB of scoped VMEM the chip
    gives a kernel (the limit stated to the compiler, which would allow a
    described chip more), each call says the layout the head's width
    picks and that its key/value heads are read by their groups, and each
    lists the cell's tiles (a head's count times batch x 32 heads: the
    ``dk/dv`` launch runs over the key/value heads and takes a group's
    heads inside a key block's run, the same tiles in another order) and
    the pairs its live strips compute: a diagonal tile's dead strips are
    no part of them."""
    import functools

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from analytics_zoo_tpu.common import profiling
    from analytics_zoo_tpu.ops import autotune
    from analytics_zoo_tpu.ops import flash_attention as fa
    from analytics_zoo_tpu.ops.flash_attention import (BlockDiffusionMask,
                                                       flash_attention)

    monkeypatch.setattr(pl, "pallas_call", functools.partial(
        pl.pallas_call,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=16 << 20)))

    b, s, h, d = shape
    blocks = autotune.untuned_blocks(d, jnp.bfloat16)
    assert blocks == (1024, 1024)
    mask = BlockDiffusionMask(s // 2, 4) if masking == "block_diffusion" \
        else None
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, s, kv_heads, d), jnp.bfloat16,
                             sharding=one_chip)
    grad = jax.jit(jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, mask is None, *blocks,
                                         mask)
                         .astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1, 2)))
    with compiled_outside_the_cache():
        compiled = grad.lower(q, k, k).compile()
    text = compiled.as_text()
    dq, dk, dv = jax.eval_shape(grad, q, k, k)
    assert dq.shape == shape and dk.shape == dv.shape == k.shape
    assert profiling.count_kernel_calls(text) == {
        **dict.fromkeys(profiling.KERNEL_FUNCTIONS, 0),
        **dict.fromkeys(profiling.FLASH_KERNELS, 1)}
    assert profiling.count_flash_layouts(text) == {
        f"{kernel}@{layout},grouped": 1
        for kernel in profiling.FLASH_KERNELS}
    assert profiling.count_flash_grid_steps(text) == {
        f"{kernel}/{kind}": n * b * h
        for kernel in profiling.FLASH_KERNELS
        for kind, n in zip(profiling.TILE_KINDS, tiles)}
    # the score pairs each call says its live strips compute and the mask
    # allows survive the compiler beside them: 96.98% and 94.16% wanted at
    # 256-wide strips (88.9% and 80.0% of whole tiles)
    per_head = fa.tile_pairs(s // 1024, s // 1024, 1024, 1024,
                             mask or fa.CausalMask(0), None)
    assert per_head[0] == computed[fa.STRIP] < sum(tiles) * 1024 * 1024
    assert profiling.count_flash_score_pairs(text) == {
        f"{kernel}/{key}": n * b * h
        for kernel in profiling.FLASH_KERNELS
        for key, n in zip(("pairs", "allowed"), per_head)}


@pytest.mark.parametrize("heads", [32, 4, 1], ids=["q", "k", "one_head"])
def test_v5e_compiles_the_norm_rotary_kernels_at_the_cells_size(one_chip,
                                                                heads):
    """The q/k norm and rotary positions of the block-diffusion cell, q at
    32 heads of 128 and k at 4, over 16,384 rows, forward and backward
    compiled for the chip at the blocks the rows are given by default:
    they fit the kernels' VMEM, and each is one launch. One head a row is
    the narrowest: its 2 MiB would be 8,192 rows, which ``MAX_ROWS``
    keeps to 512 (at 8,192 the compile runs out of VMEM)."""
    from analytics_zoo_tpu.common import profiling
    from analytics_zoo_tpu.ops import norm_rotary

    x = jax.ShapeDtypeStruct((1, 16384, heads * 128), jnp.bfloat16,
                             sharding=one_chip)
    scale = jax.ShapeDtypeStruct((128,), jnp.float32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((16384, 128), jnp.float32,
                                 sharding=one_chip)
    grad = jax.jit(jax.grad(
        lambda x, scale, table: (norm_rotary.norm_rotary(
            x, scale, table, heads, 1e-6).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1)))
    with compiled_outside_the_cache():
        text = grad.lower(x, scale, table).compile().as_text()
    assert profiling.count_kernel_calls(text) == {
        **dict.fromkeys(profiling.KERNEL_FUNCTIONS, 0),
        "norm_rotary_fwd": 1, "norm_rotary_bwd": 1}


def test_v5e_the_layer_hands_q_and_k_to_the_kernels_as_rows(one_chip,
                                                            monkeypatch):
    """One ``GroupedQueryAttention`` at the block-diffusion cell's widths
    (hidden 2,048, 32 / 4 heads of 128, repeated positions) over 4,096
    rows, its gradient under the decoder's policy compiled for the chip:
    q and k go from the projections through the norm-rotary kernels to
    the flash kernels with no copy, reshape or transpose of q, k, dq or
    dk on either side, and no float32 copy of them at all — the
    ``[b, s, h, d]`` view is no instruction. Four forward launches (q and
    k, forward and recomputed) and two backward."""
    from analytics_zoo_tpu.common import profiling
    from analytics_zoo_tpu.ops import autotune
    from analytics_zoo_tpu.ops import flash_attention as fa
    from analytics_zoo_tpu.text import hybrid_decoder

    s, h, g, d, hidden = 4096, 32, 4, 128, 2048
    mask = fa.BlockDiffusionMask(s // 2, 4)
    positions = np.tile(np.arange(s // 2, dtype=np.int32), 2)

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x):
            return attention_lib.GroupedQueryAttention(
                h, g, d, 1e6, 1e-6, dtype=jnp.bfloat16,
                name="attention")(x, positions, mask)

    layer = nn.remat(Block, policy=hybrid_decoder._BLOCK_POLICY)()
    x = jax.ShapeDtypeStruct((1, s, hidden), jnp.bfloat16, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, s, hidden), jnp.bfloat16))))
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    monkeypatch.setattr(autotune, "on_tpu", lambda: True)
    monkeypatch.setattr(autotune, "attention_decision", lambda *a: None)
    monkeypatch.setattr(autotune, "SCORES_SWITCH", 0)
    grad = jax.jit(jax.grad(
        lambda p, x: (layer.apply(p, x).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1)))
    with compiled_outside_the_cache():
        text = grad.lower(params, x).compile().as_text()
    assert profiling.count_kernel_calls(text) == {
        "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
        "norm_rotary_fwd": 4, "norm_rotary_bwd": 2,
        "moe_pack_rows": 0, "moe_sum_rows": 0}
    shapes = "|".join(re.escape(f"[1,{s},{w}]") for w in (h * d, g * d)) \
        + "|" + "|".join(re.escape(f"[1,{s},{n},{d}]") for n in (h, g))
    moved = re.findall(rf"= bf16(?:{shapes})\S* (copy|reshape|transpose)\(",
                       text)
    assert not moved
    assert not re.findall(rf"= f32(?:{shapes})", text)


@pytest.mark.parametrize("head_dim,dtype", [
    (64, jnp.bfloat16), (256, jnp.bfloat16), (512, jnp.bfloat16),
    (128, jnp.float32), (512, jnp.float32)],
    ids=["bf16_64", "bf16_256", "bf16_512", "fp32_128", "fp32_512"])
def test_v5e_holds_the_untuned_blocks_at_every_head_row(one_chip, head_dim,
                                                        dtype):
    """The blocks a shape without a verdict runs at fit the kernels' VMEM,
    forward and backward, from the decoder's 64-wide bfloat16 head to the
    widest the kernels take: 1,024 x 1,024 while the head's row leaves
    the float32 score tile its 4 MB, 512 x 512 beyond."""
    from analytics_zoo_tpu.ops import autotune
    from analytics_zoo_tpu.ops.flash_attention import flash_attention

    blocks = autotune.untuned_blocks(head_dim, dtype)
    row = max(head_dim, 128) * jnp.dtype(dtype).itemsize
    assert blocks == ((1024, 1024) if row <= 512 else (512, 512))
    q = jax.ShapeDtypeStruct((1, 2048, 1, head_dim), dtype,
                             sharding=one_chip)
    grad = jax.jit(jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, True, *blocks)
                         .astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1, 2)))
    with compiled_outside_the_cache():
        grad.lower(q, q, q).compile()


@pytest.mark.parametrize("kernels", [True, False],
                         ids=["the_kernels", "the_gathers"])
def test_v5e_compiles_the_expert_layers_combine_at_the_cells_widths(
        one_chip, monkeypatch, kernels):
    """One dropless expert layer of the block-diffusion cell (16,384
    tokens, hidden 2,048, 16 of 128 experts of width 768 held, ``k`` 8),
    its gradient compiled for the chip under the 16 MiB of scoped VMEM the
    chip gives a kernel: the combine (``_put_rows``' forward,
    ``_take_rows``' backward) is the pack and sum kernels, a launch of
    each per window each way (the second window's inside the ``cond``),
    and the entry computation holds no ``bf16[16384, 2048]`` gather. The
    gathers it replaces read 16 there: ``k`` a direction."""
    import dataclasses

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from analytics_zoo_tpu.common import profiling
    from analytics_zoo_tpu.ops import flash_attention as fa
    from analytics_zoo_tpu.ops import moe, moe_combine

    call = pl.pallas_call

    def stated(*args, compiler_params=None, **kwargs):
        params = dataclasses.replace(
            compiler_params or pltpu.CompilerParams(),
            vmem_limit_bytes=16 << 20)
        return call(*args, compiler_params=params, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", stated)
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    if not kernels:
        monkeypatch.setattr(moe_combine, "engages", lambda y: False)
    n, hidden, width, held, experts, k = 16384, 2048, 768, 16, 128, 8

    def loss(x, w1, w3, w2, logits):
        ids, weights = moe.sigmoid_top_k_routing(
            logits, jnp.zeros((experts,)), k)
        out, _, _ = moe.held_expert_ffn(x, ids, weights, w1, w3, w2,
                                        tuple(range(held)), experts)
        return (out.astype(jnp.float32) ** 2).sum()

    def aval(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    with compiled_outside_the_cache():
        text = grad.lower(
            aval((n, hidden), jnp.bfloat16), aval((held, hidden, width)),
            aval((held, hidden, width)), aval((held, width, hidden)),
            aval((n, experts))).compile().as_text()
    launches = 4 if kernels else 0
    assert profiling.count_kernel_calls(text) == {
        **dict.fromkeys(profiling.KERNEL_FUNCTIONS, 0),
        "moe_pack_rows": launches, "moe_sum_rows": launches}
    entry = text.split("\nENTRY", 1)[1]
    gathers = re.findall(r'= bf16\[16384,2048\]\S* fusion\(.*op_name="[^"]*'
                         r'/gather"', entry)
    assert len(gathers) == (0 if kernels else 2 * k)
