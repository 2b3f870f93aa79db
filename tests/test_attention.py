import numpy as np
import pytest


def _qkv(b=2, s=32, h=2, d=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: rng.normal(size=(b, s, h, d)).astype(np.float32)
    return mk(), mk(), mk()


def _reference(q, k, v, causal=False):
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.attention import _reference_attention
    return np.asarray(_reference_attention(jnp.asarray(q), jnp.asarray(k),
                                           jnp.asarray(v), causal=causal))


def test_blockwise_matches_reference(orca_ctx):
    from analytics_zoo_tpu.ops.flash_attention import blockwise_attention
    q, k, v = _qkv()
    for causal in (False, True):
        ref = _reference(q, k, v, causal)
        out = np.asarray(blockwise_attention(q, k, v, causal=causal, block_k=8))
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_blockwise_ragged_seq(orca_ctx):
    from analytics_zoo_tpu.ops.flash_attention import blockwise_attention
    q, k, v = _qkv(s=20)  # not a multiple of block_k
    ref = _reference(q, k, v, True)
    out = np.asarray(blockwise_attention(q, k, v, causal=True, block_k=8))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_blockwise_grad_matches(orca_ctx):
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.flash_attention import blockwise_attention
    from analytics_zoo_tpu.ops.attention import _reference_attention
    q, k, v = _qkv(b=1, s=16, h=1, d=4)

    def loss_block(q, k, v):
        return blockwise_attention(q, k, v, causal=True, block_k=8).sum()

    def loss_ref(q, k, v):
        return _reference_attention(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), causal=True).sum()

    g1 = jax.grad(loss_block, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_ring_attention_matches_full(orca_ctx):
    from analytics_zoo_tpu.parallel.strategy import ShardingStrategy
    from analytics_zoo_tpu.parallel.mesh import place_on_mesh
    from analytics_zoo_tpu.ops.ring_attention import ring_attention
    from jax.sharding import PartitionSpec as P

    s = ShardingStrategy.parse("dp2,sp4")
    mesh = s.build_mesh()
    q, k, v = _qkv(b=4, s=32, h=2, d=8)
    spec_fn = lambda a: P("data", "seq", None, None)
    gq, gk, gv = (place_on_mesh(t, mesh, spec_fn) for t in (q, k, v))

    for causal in (False, True):
        out = np.asarray(ring_attention(gq, gk, gv, mesh=mesh, causal=causal,
                                        batch_axis="data"))
        ref = _reference(q, k, v, causal)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_ring_attention_seq_only_mesh(orca_ctx):
    from analytics_zoo_tpu.parallel.strategy import ShardingStrategy
    from analytics_zoo_tpu.parallel.mesh import place_on_mesh
    from analytics_zoo_tpu.ops.ring_attention import ring_attention
    from jax.sharding import PartitionSpec as P

    s = ShardingStrategy.parse("sp8")
    mesh = s.build_mesh()
    q, k, v = _qkv(b=2, s=64, h=2, d=8, seed=3)
    spec_fn = lambda a: P(None, "seq", None, None)
    gq, gk, gv = (place_on_mesh(t, mesh, spec_fn) for t in (q, k, v))
    out = np.asarray(ring_attention(gq, gk, gv, mesh=mesh, causal=True))
    ref = _reference(q, k, v, True)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_ring_attention_grad(orca_ctx):
    """Ring attention must be differentiable (it sits inside train steps)."""
    import jax
    from analytics_zoo_tpu.parallel.strategy import ShardingStrategy
    from analytics_zoo_tpu.parallel.mesh import place_on_mesh
    from analytics_zoo_tpu.ops.ring_attention import ring_attention
    from analytics_zoo_tpu.ops.attention import _reference_attention
    from jax.sharding import PartitionSpec as P
    import jax.numpy as jnp

    s = ShardingStrategy.parse("sp4")
    mesh = s.build_mesh(devices=jax.devices()[:4])
    q, k, v = _qkv(b=1, s=16, h=1, d=4, seed=5)
    spec_fn = lambda a: P(None, "seq", None, None)
    gq, gk, gv = (place_on_mesh(t, mesh, spec_fn) for t in (q, k, v))

    g1 = jax.grad(lambda q, k, v: ring_attention(
        q, k, v, mesh=mesh, causal=False).sum(), argnums=(0, 1, 2))(gq, gk, gv)
    g2 = jax.grad(lambda q, k, v: _reference_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_flash_kernel_interpret_mode(orca_ctx):
    """Pallas kernel numerics vs reference, in interpret mode on CPU."""
    import jax.experimental.pallas as pl
    from analytics_zoo_tpu.ops import flash_attention as fa
    import functools
    import jax

    q, k, v = _qkv(b=1, s=256, h=2, d=128, seed=7)
    # run the pallas_call in interpret mode by monkeypatching pallas_call
    orig = pl.pallas_call
    try:
        pl.pallas_call = functools.partial(orig, interpret=True)
        out = np.asarray(fa._flash_fwd(q, k, v, causal=True,
                                       block_q=128, block_k=128))
    finally:
        pl.pallas_call = orig
    ref = _reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-4)


def test_flash_backward_kernel_interpret_mode(orca_ctx):
    """FlashAttention-2 backward kernels (dq + dk/dv over the saved
    logsumexp) vs the blockwise vjp, interpret mode on CPU — exact in
    fp32, bf16-rounding otherwise. Also checks the lse the forward
    saves."""
    import functools
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl
    from analytics_zoo_tpu.ops import flash_attention as fa

    orig = pl.pallas_call
    try:
        pl.pallas_call = functools.partial(orig, interpret=True)
        for causal in (False, True):
            q, k, v = _qkv(b=2, s=256, h=2, d=128, seed=11 + causal)
            g = np.asarray(jax.random.normal(
                jax.random.PRNGKey(3), (2, 256, 2, 128)), np.float32)

            # call the kernels DIRECTLY: flash_attention's vjp would
            # silently fall back to the blockwise reference on a broken
            # kernel, making the comparison vacuous
            out, lse = fa._flash_fwd(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal=causal,
                                     block_q=128, block_k=128,
                                     return_lse=True)
            gf = fa._flash_bwd(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), out, lse, jnp.asarray(g),
                               causal, 128, 128)

            def f_block(q, k, v):
                return (fa.blockwise_attention(
                    jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                    causal=causal) * jnp.asarray(g)).sum()

            gb = jax.grad(f_block, argnums=(0, 1, 2))(q, k, v)
            for name, a, b in zip("qkv", gf, gb):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4,
                    err_msg=f"d{name} causal={causal}")
            # the saved lse must equal the true logsumexp of scaled scores
            scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(128)
            if causal:
                mask = np.tril(np.ones((256, 256), bool))
                scores = np.where(mask[None, None], scores, -1e30)
            ref_lse = np.log(np.exp(
                scores - scores.max(-1, keepdims=True)).sum(-1))                 + scores.max(-1)
            np.testing.assert_allclose(
                np.asarray(lse).reshape(2, 2, 256),
                ref_lse.astype(np.float32), rtol=1e-4, atol=1e-4)
    finally:
        pl.pallas_call = orig


def test_flash_head_dim_64_parity(orca_ctx, monkeypatch):
    """head_dim 64 (the BERT class) packs into the 128 lane: forward
    parity vs the reference, full and causal, plus a ragged sequence
    (s % block != 0 — the padded tail k-block must mask to −∞, ISSUE 8
    satellite). Runs via ZOO_PALLAS_INTERPRET so the real kernel bodies
    execute on CPU, exercising the same knob docs/kernels.md documents."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import flash_attention as fa

    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    for s, causal in ((256, False), (256, True), (200, True), (40, False)):
        q, k, v = _qkv(b=1, s=s, h=2, d=64, seed=17 + s)
        out = np.asarray(fa.flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal,
            128, 128))
        ref = _reference(q, k, v, causal)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-4,
                                   err_msg=f"s={s} causal={causal}")


def test_flash_head_dim_64_backward(orca_ctx, monkeypatch):
    """FA-2 backward kernels at head_dim 64, aligned AND ragged seq: the
    kernels are called directly (the custom_vjp would silently fall back
    to blockwise on a broken kernel, making the comparison vacuous).
    Padded lse rows carry +1e30 so padded-row p is exactly 0 — grads for
    real rows must match the blockwise vjp."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import flash_attention as fa

    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    for s, causal in ((256, True), (200, False), (200, True)):
        q, k, v = _qkv(b=1, s=s, h=2, d=64, seed=29 + s)
        g = np.asarray(jax.random.normal(
            jax.random.PRNGKey(31), (1, s, 2, 64)), np.float32)
        out, lse = fa._flash_fwd(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), causal=causal,
                                 block_q=128, block_k=128,
                                 return_lse=True)
        gf = fa._flash_bwd(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           out, lse, jnp.asarray(g), causal, 128, 128)

        def f_block(q, k, v):
            return (fa.blockwise_attention(q, k, v, causal=causal)
                    * jnp.asarray(g)).sum()

        gb = jax.grad(f_block, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for name, a, b in zip("qkv", gf, gb):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-3, atol=5e-4,
                err_msg=f"d{name} s={s} causal={causal}")


def test_flash_cross_attention_ragged_kv(orca_ctx, monkeypatch):
    """Cross-attention with sq < sk and a ragged kv length (the KV-cache
    decode shape): the causal offset comes from the ORIGINAL lengths —
    bottom-right alignment must not shift when the tail k-block pads.
    sq stays <= sk: a causal query with ZERO visible keys is degenerate
    (every implementation emits a different 'uniform' placeholder)."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import flash_attention as fa

    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(41)
    q = rng.normal(size=(1, 16, 2, 64)).astype(np.float32)
    k = rng.normal(size=(1, 24, 2, 64)).astype(np.float32)
    v = rng.normal(size=(1, 24, 2, 64)).astype(np.float32)
    for causal in (False, True):
        out = np.asarray(fa.flash_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal, 16, 16))
        ref = _reference(q, k, v, causal)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-4,
                                   err_msg=f"causal={causal}")


def test_default_use_flash_relaxed(orca_ctx):
    """head_dim 64 and ragged seq no longer disqualify a shape (the
    kernels pad internally); the remaining exclusions are economic:
    sub-block sequences and head dims past 512. Off-TPU always False."""
    from analytics_zoo_tpu.ops.flash_attention import (
        default_use_flash, on_tpu,
    )

    on_tpu = on_tpu()
    # CPU test env: the gate must still say no (pallas needs the TPU)
    assert default_use_flash(2048, 64) == on_tpu
    assert default_use_flash(2000, 64) == on_tpu   # ragged seq eligible
    assert not default_use_flash(64, 64)           # shorter than a block
    assert not default_use_flash(2048, 1024)       # VMEM pressure


def test_ring_flash_composition(orca_ctx):
    """ring_attention(use_flash=True): each resident block runs the
    pallas kernels and ring steps merge via logsumexp (the lse cotangent
    flows through flash_attention_with_lse's backward). Forward AND
    gradients must match blockwise over the full sequence."""
    import functools
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl
    from jax.sharding import NamedSharding, PartitionSpec as P
    from analytics_zoo_tpu.parallel.strategy import ShardingStrategy
    from analytics_zoo_tpu.ops.ring_attention import ring_attention
    from analytics_zoo_tpu.ops.flash_attention import blockwise_attention

    mesh = ShardingStrategy.parse("sp8").build_mesh()
    key = jax.random.PRNGKey(0)
    B, S, H, D = 1, 1024, 1, 128
    q, k, v = (np.asarray(jax.random.normal(kk, (B, S, H, D)), np.float32)
               for kk in jax.random.split(key, 3))
    sh = NamedSharding(mesh, P(None, "seq", None, None))
    gq, gk, gv = (jax.device_put(a, sh) for a in (q, k, v))
    g = np.asarray(jax.random.normal(jax.random.PRNGKey(9),
                                     (B, S, H, D)), np.float32)

    orig = pl.pallas_call
    try:
        pl.pallas_call = functools.partial(orig, interpret=True)
        for causal in (False, True):
            out = np.asarray(ring_attention(gq, gk, gv, mesh=mesh,
                                            causal=causal, use_flash=True))
            ref = np.asarray(blockwise_attention(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                causal=causal))
            np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
            gr = jax.grad(lambda q, k, v: (ring_attention(
                q, k, v, mesh=mesh, causal=causal, use_flash=True)
                * jnp.asarray(g)).sum(), argnums=(0, 1, 2))(gq, gk, gv)
            gb = jax.grad(lambda q, k, v: (blockwise_attention(
                q, k, v, causal=causal) * jnp.asarray(g)).sum(),
                argnums=(0, 1, 2))(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v))
            for name, a, b in zip("qkv", gr, gb):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-3, atol=5e-4,
                    err_msg=f"d{name} causal={causal}")
    finally:
        pl.pallas_call = orig


class TestCausalCrossLength:
    """Regression: causal mask must be bottom-right aligned (KV-cache decode
    semantics) in every implementation, not just _reference_attention."""

    def test_blockwise_matches_reference_when_sq_ne_sk(self):
        import numpy as np
        import jax
        from analytics_zoo_tpu.ops.attention import _reference_attention
        from analytics_zoo_tpu.ops.flash_attention import blockwise_attention

        rng = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (1, 4, 2, 8))
        k = jax.random.normal(kk, (1, 8, 2, 8))
        v = jax.random.normal(kv, (1, 8, 2, 8))
        ref = _reference_attention(q, k, v, causal=True)
        blk = blockwise_attention(q, k, v, causal=True, block_k=4)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(blk),
                                   rtol=2e-5, atol=2e-5)


def test_ulysses_flash_composition(orca_ctx):
    """ulysses_attention(use_flash=True): per-device full attention runs
    the pallas kernels after the seq->head all-to-all; fwd + grads match
    the einsum path."""
    import functools
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl
    from jax.sharding import NamedSharding, PartitionSpec as P
    from analytics_zoo_tpu.parallel.strategy import ShardingStrategy
    from analytics_zoo_tpu.ops.ulysses import ulysses_attention

    mesh = ShardingStrategy.parse("sp2").build_mesh()
    key = jax.random.PRNGKey(1)
    B, S, H, D = 1, 256, 2, 128
    q, k, v = (np.asarray(jax.random.normal(kk, (B, S, H, D)), np.float32)
               for kk in jax.random.split(key, 3))
    sh = NamedSharding(mesh, P(None, "seq", None, None))
    gq, gk, gv = (jax.device_put(a, sh) for a in (q, k, v))
    g = np.asarray(jax.random.normal(jax.random.PRNGKey(5),
                                     (B, S, H, D)), np.float32)

    orig = pl.pallas_call
    try:
        pl.pallas_call = functools.partial(orig, interpret=True)
        for causal in (False, True):
            out = np.asarray(ulysses_attention(gq, gk, gv, mesh=mesh,
                                               causal=causal,
                                               use_flash=True))
            ref = np.asarray(ulysses_attention(gq, gk, gv, mesh=mesh,
                                               causal=causal,
                                               use_flash=False))
            np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
            gr = jax.grad(lambda q, k, v: (ulysses_attention(
                q, k, v, mesh=mesh, causal=causal, use_flash=True)
                * jnp.asarray(g)).sum(), argnums=(0, 1, 2))(gq, gk, gv)
            gb = jax.grad(lambda q, k, v: (ulysses_attention(
                q, k, v, mesh=mesh, causal=causal, use_flash=False)
                * jnp.asarray(g)).sum(), argnums=(0, 1, 2))(gq, gk, gv)
            for name, a, b in zip("qkv", gr, gb):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-3, atol=5e-4,
                    err_msg=f"d{name} causal={causal}")
    finally:
        pl.pallas_call = orig


def test_ulysses_matches_full(orca_ctx):
    """All-to-all sequence parallelism: sequence-sharded q/k/v through two
    all-to-alls + local full attention must equal single-device
    attention."""
    from analytics_zoo_tpu.parallel.strategy import ShardingStrategy
    from analytics_zoo_tpu.parallel.mesh import place_on_mesh
    from analytics_zoo_tpu.ops.ulysses import ulysses_attention
    from jax.sharding import PartitionSpec as P

    s = ShardingStrategy.parse("dp2,sp4")
    mesh = s.build_mesh()
    q, k, v = _qkv(b=4, s=32, h=4, d=8)   # heads divisible by sp=4
    spec_fn = lambda a: P("data", "seq", None, None)  # noqa: E731
    gq, gk, gv = (place_on_mesh(t, mesh, spec_fn) for t in (q, k, v))

    for causal in (False, True):
        out = np.asarray(ulysses_attention(gq, gk, gv, mesh=mesh,
                                           causal=causal,
                                           batch_axis="data"))
        ref = _reference(q, k, v, causal)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_ulysses_grad_matches(orca_ctx):
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.parallel.strategy import ShardingStrategy
    from analytics_zoo_tpu.parallel.mesh import place_on_mesh
    from analytics_zoo_tpu.ops.ulysses import ulysses_attention
    from analytics_zoo_tpu.ops.attention import _reference_attention
    from jax.sharding import PartitionSpec as P

    s = ShardingStrategy.parse("sp4")
    mesh = s.build_mesh()
    q, k, v = _qkv(b=2, s=16, h=4, d=4)
    spec_fn = lambda a: P(None, "seq", None, None)  # noqa: E731
    gq, gk, gv = (place_on_mesh(t, mesh, spec_fn) for t in (q, k, v))

    def loss_u(q, k, v):
        return ulysses_attention(q, k, v, mesh=mesh, causal=True).sum()

    def loss_ref(q, k, v):
        return _reference_attention(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), causal=True).sum()

    gu = jax.grad(loss_u, argnums=(0, 1, 2))(gq, gk, gv)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gu, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_ulysses_validates_divisibility(orca_ctx):
    from analytics_zoo_tpu.parallel.strategy import ShardingStrategy
    from analytics_zoo_tpu.ops.ulysses import ulysses_attention

    s = ShardingStrategy.parse("sp4")
    mesh = s.build_mesh()
    q, k, v = _qkv(b=2, s=16, h=3, d=4)   # 3 heads % 4 != 0
    with pytest.raises(ValueError, match="divide"):
        ulysses_attention(q, k, v, mesh=mesh)


class TestSelfAttentionFlag:
    """AttentionModule.self_attention: the packed-QKV path must be
    forceable — the ``kv_in is q_in`` identity fallback does not survive
    transforms that rebind arguments (checkpoint/vmap hand the module two
    distinct tracers for the same value)."""

    def _setup(self, **kw):
        import jax
        from analytics_zoo_tpu.ops.attention import AttentionModule
        m = AttentionModule(num_heads=2, head_dim=8, **kw)
        x = np.random.default_rng(5).normal(
            size=(2, 16, 32)).astype(np.float32)
        params = m.init(jax.random.PRNGKey(0), x)
        return m, params, x

    @staticmethod
    def _n_dots(fn, *args):
        import jax
        return str(jax.make_jaxpr(fn)(*args)).count("dot_general")

    def test_flag_survives_argument_rebinding(self, orca_ctx):
        import jax  # noqa: F401
        m, params, x = self._setup()
        forced, _, _ = self._setup(self_attention=True)
        # identity fallback: a DISTINCT array for the same value silently
        # demotes to three projection matmuls (+2 dot_generals)
        packed = self._n_dots(lambda a: m.apply(params, a), x)
        demoted = self._n_dots(lambda a, b: m.apply(params, a, b), x,
                               x.copy())
        assert demoted == packed + 2
        # the explicit flag keeps the fused matmul through the rebinding
        still_packed = self._n_dots(
            lambda a, b: forced.apply(params, a, b), x, x.copy())
        assert still_packed == packed
        # and the result is bit-identical to plain self-attention
        np.testing.assert_array_equal(
            np.asarray(forced.apply(params, x, x.copy())),
            np.asarray(m.apply(params, x)))

    def test_flag_false_forces_separate_projections(self, orca_ctx):
        m, params, x = self._setup()
        off, _, _ = self._setup(self_attention=False)
        packed = self._n_dots(lambda a: m.apply(params, a), x)
        unpacked = self._n_dots(lambda a: off.apply(params, a), x)
        assert unpacked == packed + 2
        # both formulations compute the same attention (same params, the
        # packed concat is exact) — numerics agree to float tolerance
        np.testing.assert_allclose(np.asarray(off.apply(params, x)),
                                   np.asarray(m.apply(params, x)),
                                   rtol=1e-5, atol=1e-6)


def test_pallas_interpret_is_refused_on_a_tpu_backend(monkeypatch):
    """On the chip the kernels compile; a forgotten ZOO_PALLAS_INTERPRET
    must not let the interpreter pass for them."""
    from analytics_zoo_tpu.ops import flash_attention as fa

    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    assert fa.pallas_interpret() is True            # honoured off the TPU
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="ZOO_PALLAS_INTERPRET"):
        fa.pallas_interpret()
    monkeypatch.delenv("ZOO_PALLAS_INTERPRET")
    assert fa.pallas_interpret() is False           # unset: fine on a TPU


def test_flash_backward_has_no_fallback(orca_ctx, monkeypatch):
    """A backward that cannot build raises: there is no warn-and-
    rematerialise branch left to hide it (off the TPU, without the
    interpreter, the pallas call itself fails)."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import flash_attention as fa

    assert not hasattr(fa, "_bwd_with_fallback")
    monkeypatch.delenv("ZOO_PALLAS_INTERPRET", raising=False)
    q, k, v = (jnp.asarray(a) for a in _qkv(b=1, s=128, h=1, d=128))
    with pytest.raises(Exception):
        jax.grad(lambda q: fa.flash_attention(q, k, v).sum())(q)


def test_blockwise_scores_are_fp32_off_the_matmul(orca_ctx):
    """bf16 inputs: the scores never round through bf16 (on a v5e that
    rounding gave NaN dq/dk whenever the scan ran more than one key
    block). Structural check — the bf16 einsum asks for an fp32 result."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops.flash_attention import blockwise_attention

    q = jnp.ones((1, 256, 1, 128), jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(
        lambda q: blockwise_attention(q, q, q, block_k=128))(q))
    assert "preferred_element_type=float32" in jaxpr
    g = jax.grad(lambda q: blockwise_attention(
        q, q, q, causal=True, block_k=128).astype(jnp.float32).sum())(q)
    assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))


# ------------------------------------------- the kernels' table of tiles

def _tiles_by_position(nq, nk, bq, bk, causal, off, kv_len):
    """``{(qi, ki): kind}`` told the slow way: from the mask of every
    position of every tile."""
    from analytics_zoo_tpu.ops import flash_attention as fa

    kinds = {}
    for qi in range(nq):
        for ki in range(nk):
            q_pos = qi * bq + np.arange(bq)[:, None]
            k_pos = ki * bk + np.arange(bk)[None, :]
            out = np.zeros((bq, bk), bool)
            if causal:
                out |= k_pos > q_pos + off
            dead = out.all()
            if kv_len is not None:
                out = out | (k_pos >= kv_len)
            kinds[qi, ki] = fa.DEAD if dead else (
                fa.DIAGONAL if out.any() else fa.INTERIOR)
    return kinds


#: id → (sq, sk, block_q, block_k, causal); the lengths are padded to the
#: blocks as ``_pad_blocks`` pads them
TABLES = {
    "8192_causal_at_512x512": (8192, 8192, 512, 512, True),
    "8192_causal_at_128x128": (8192, 8192, 128, 128, True),
    "not_causal": (384, 640, 128, 128, False),
    "not_causal_ragged_keys": (256, 300, 128, 128, False),
    "more_keys_than_queries": (256, 512, 128, 128, True),
    "fewer_keys_than_queries": (320, 128, 128, 128, True),
    "ragged_causal": (200, 200, 128, 128, True),
    "unequal_blocks": (512, 512, 256, 128, True),
}


@pytest.mark.parametrize("case", list(TABLES))
def test_tile_table_lists_live_tiles_in_the_rectangular_order(case):
    """Pure Python. Each head's grid steps are the tiles that hold work,
    once each, in the order a rectangular grid would reach them (forward
    and ``dq``: for each query block its key blocks ascending; ``dk/dv``:
    for each key block its query blocks ascending); a tile no mask touches
    is interior; a resident block with no live tile keeps one dead
    step."""
    from analytics_zoo_tpu.ops import flash_attention as fa

    sq, sk, bq, bk, causal = TABLES[case]
    nq, nk = -(-sq // bq), -(-sk // bk)
    off, kv_len = sk - sq, (sk if sk % bk else None)
    want = _tiles_by_position(nq, nk, bq, bk, causal, off, kv_len)
    for key_major in (False, True):
        table = fa.tile_table(nq, nk, bq, bk, fa.static_mask(causal, None,
                                                             sq, sk),
                              kv_len, key_major=key_major)
        assert table.dtype == np.int32 and table.shape[1] == 3
        rows = [tuple(r) for r in table.tolist()]
        live = [(qi, ki, kind) for (qi, ki), kind in want.items()
                if kind != fa.DEAD]
        order = (lambda r: (r[1], r[0])) if key_major else \
            (lambda r: (r[0], r[1]))
        assert [r for r in rows if r[2] != fa.DEAD] == sorted(live, key=order)
        # a kept dead step stands where its block's run would have stood,
        # and only for a block with no live tile
        resident = 1 if key_major else 0
        runs = [r[resident] for r in rows]
        assert runs == sorted(runs)
        assert set(runs) == set(range(nk if key_major else nq))
        for r in rows:
            if r[2] == fa.DEAD:
                assert want[r[0], r[1]] == fa.DEAD
                assert runs.count(r[resident]) == 1
    per_head = np.bincount(
        fa.tile_table(nq, nk, bq, bk, fa.static_mask(causal, None, sq, sk),
                      kv_len)[:, 2], minlength=3).tolist()
    assert per_head == {
        "8192_causal_at_512x512": [120, 16, 0],
        "8192_causal_at_128x128": [2016, 64, 0],
        "not_causal": [15, 0, 0],
        "not_causal_ragged_keys": [4, 2, 0],
        "more_keys_than_queries": [5, 2, 0],
        "fewer_keys_than_queries": [0, 2, 1],
        "ragged_causal": [1, 2, 0],
        "unequal_blocks": [2, 4, 0],
    }[case]


#: id → (sq, sk, block_q, block_k, causal, the kinds of step the case is
#: there for)
SCHEDULES = {
    "causal_4x4_tiles": (512, 512, 128, 128, True, {0, 1}),
    "more_keys_than_queries": (256, 512, 128, 128, True, {0, 1}),
    "query_blocks_that_see_no_key": (320, 128, 128, 128, True, {1, 2}),
    "last_key_block_masked_by_its_tail_alone": (
        256, 300, 128, 128, False, {0, 1}),
    "not_causal": (256, 384, 128, 128, False, {0}),
    "unequal_blocks": (512, 512, 256, 128, True, {0, 1}),
    "ragged_causal": (200, 200, 128, 128, True, {0, 1}),
}


@pytest.mark.parametrize("case", list(SCHEDULES))
def test_flash_schedule_matches_blockwise_and_its_vjp(orca_ctx, monkeypatch,
                                                      case):
    """The real kernel bodies, interpreted, over the table of live tiles:
    output, logsumexp, ``dq``, ``dk``, ``dv`` (the logsumexp's cotangent
    included, through ``flash_attention_with_lse``) against
    ``blockwise_attention`` and its vjp. Rows that see no key at all are
    degenerate (every implementation's placeholder differs): they carry a
    zero cotangent, their outputs must be written and finite, and nothing
    else is asked of them."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import flash_attention as fa

    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    sq, sk, bq, bk, causal, kinds = SCHEDULES[case]
    _, _, _, bq_p, bk_p, sq_p, sk_p, _ = fa._pad_blocks(
        *(jnp.zeros((1, s, 1, 64)) for s in (sq, sk, sk)), bq, bk)
    table = fa.tile_table(sq_p // bq_p, sk_p // bk_p, bq_p, bk_p,
                          fa.static_mask(causal, None, sq, sk),
                          sk if sk_p != sk else None)
    assert set(table[:, 2].tolist()) == kinds
    rng = np.random.default_rng(sq + sk + bq)
    b, h, d = 1, 2, 64
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(b, sk, h, d)), jnp.float32)
            for _ in range(2))
    blind = max(0, sq - sk) if causal else 0       # rows that see no key
    g = rng.normal(size=(b, sq, h, d)).astype(np.float32)
    g_lse = rng.normal(size=(b * h, sq)).astype(np.float32)
    g[:, :blind], g_lse[:, :blind] = 0.0, 0.0

    got, vjp = jax.vjp(lambda q, k, v: fa.flash_attention_with_lse(
        q, k, v, causal, bq, bk), q, k, v)
    got_grads = vjp((jnp.asarray(g), jnp.asarray(g_lse)))
    want, ref_vjp = jax.vjp(lambda q, k, v: fa.blockwise_attention(
        q, k, v, causal=causal, block_k=64, return_lse=True), q, k, v)
    want_grads = ref_vjp((jnp.asarray(g), jnp.asarray(g_lse)))

    for a in (*got, *got_grads):
        assert a.shape[1] in (sq, sk) and bool(jnp.all(jnp.isfinite(a)))
    tol = dict(rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(got[0][:, blind:], want[0][:, blind:], **tol)
    np.testing.assert_allclose(got[1][:, blind:], want[1][:, blind:], **tol)
    np.testing.assert_allclose(got_grads[0][:, blind:],
                               want_grads[0][:, blind:], **tol,
                               err_msg="dq")
    for name, a, w in zip(("dk", "dv"), got_grads[1:], want_grads[1:]):
        np.testing.assert_allclose(a, w, **tol, err_msg=name)
    if 2 in kinds:
        # a query block the table keeps a dead step for: its rows are
        # zeros, as the rectangular grid left them
        assert not np.asarray(got[0][:, :bq_p]).any()
        assert not np.asarray(got_grads[0][:, :bq_p]).any()


# ------------------------- key/value heads by group, operands by layout

def _launches(fn, *args):
    """The metadata of every ``pallas_call`` in ``fn``'s jaxpr, in order,
    and the shapes its ``transpose`` equations are given."""
    import jax

    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub)

    found = list(eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    return ([dict(e.params["metadata"]) for e in found
             if e.primitive.name == "pallas_call"],
            [e.invars[0].aval.shape for e in found
             if e.primitive.name == "transpose"])


def _dense_with_lse(q, k, v, allowed):
    """Dense attention of ``q`` over k, v REPEATED to its heads, under the
    boolean ``allowed`` [sq, sk]: output and the row logsumexp as
    ``[b·h, sq]``."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import attention as attention_lib
    b, sq, h, d = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    scores = jnp.where(allowed[None, None], scores, -1e30)
    out = attention_lib._reference_attention(q, k, v, mask=allowed)
    return out, jax.nn.logsumexp(scores, axis=-1).reshape(b * h, sq)


#: the masks a grouped launch is held to: id -> (sq, sk, causal, the
#: block-diffusion mask's (L, B) or None)
GROUPED_MASKS = {
    "causal": (256, 256, True, None),
    "block_diffusion": (256, 256, False, (128, 4)),
    "unmasked_ragged_keys": (128, 200, False, None),
}


@pytest.mark.parametrize("masking", list(GROUPED_MASKS))
@pytest.mark.parametrize("head_dim", [128, 64])
@pytest.mark.parametrize("groups", [1, 4, 8])
def test_grouped_kv_through_the_three_kernels(orca_ctx, monkeypatch, groups,
                                              head_dim, masking):
    """k and v at ``kv_heads = heads // groups`` through the real kernel
    bodies, interpreted: output, logsumexp and ``dq`` against dense
    attention on REPEATED k, v, and ``dk``, ``dv`` — which come out at
    ``kv_heads`` — against that reference's gradients summed over each
    group (the logsumexp's cotangent included). Each launch says how it
    found a head's blocks: ``rows`` at a head of whole lanes (no
    head-major transpose of any operand then), ``heads`` otherwise;
    ``grouped`` when a key/value head serves more than one query head."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import flash_attention as fa

    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    sq, sk, causal, bd = GROUPED_MASKS[masking]
    mask = fa.BlockDiffusionMask(*bd) if bd else None
    b, h, d = 1, 8, head_dim
    kv_heads = h // groups
    rng = np.random.default_rng(groups + head_dim + sq)
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(b, sk, kv_heads, d)), jnp.float32)
            for _ in range(2))
    g = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    g_lse = jnp.asarray(rng.normal(size=(b * h, sq)), jnp.float32)
    if mask is not None:
        allowed = mask.dense(sq, sk)
    elif causal:
        allowed = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
    else:
        allowed = jnp.ones((sq, sk), bool)

    def flash(q, k, v):
        return fa.flash_attention_with_lse(q, k, v, causal, 128, 128, mask)

    got, vjp = jax.vjp(flash, q, k, v)
    dq, dk, dv = vjp((g, g_lse))
    want, ref_vjp = jax.vjp(
        lambda q, k, v: _dense_with_lse(q, k, v, allowed),
        q, *fa.repeat_kv_heads(q, k, v))
    want_dq, want_dk, want_dv = ref_vjp((g, g_lse))
    assert dk.shape == dv.shape == (b, sk, kv_heads, d)

    def group_sums(a):
        return a.reshape(b, sk, kv_heads, groups, d).sum(3)

    tol = dict(rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[0], want[0], **tol, err_msg="out")
    np.testing.assert_allclose(got[1], want[1], **tol, err_msg="lse")
    np.testing.assert_allclose(dq, want_dq, **tol, err_msg="dq")
    np.testing.assert_allclose(dk, group_sums(want_dk), **tol, err_msg="dk")
    np.testing.assert_allclose(dv, group_sums(want_dv), **tol, err_msg="dv")

    launches, transposed = _launches(
        lambda q, k, v: jax.vjp(flash, q, k, v)[1]((g, g_lse)), q, k, v)
    assert len(launches) == 3
    for said in launches:
        assert said["layout"] == ("rows" if head_dim % 128 == 0 else "heads")
        assert said["kv"] == ("grouped" if groups > 1 else "own")
    four_d = [s for s in transposed if len(s) == 4]
    assert bool(four_d) == (head_dim % 128 != 0)


#: the masks a diagonal tile's strips are held to: id -> (sq, sk, causal,
#: the block-diffusion mask's (L, B, noisy) or None, the blocks in strips,
#: whether a diagonal tile keeps dead strips), in units of a strip
STRIP_MASKS = {
    "causal": (4, 4, True, None, 2, True),
    "block_diffusion": (4, 4, False, (2, True), 2, True),
    "block_diffusion_clean_half": (4, 4, False, (4, False), 2, True),
    "padded_key_tail": (2, 2.25, False, None, 2, True),
    # one strip a tile: a diagonal tile has no dead strip and keeps the
    # whole-tile body
    "no_dead_strip": (4, 4, True, None, 1, False),
}


@pytest.fixture
def strips_of_128(monkeypatch):
    """Strips of 128, so that the interpreted kernels run at tiles of a
    few hundred rows: what a strip is does not depend on its side. The
    tables are cached by their arguments, which the side is not one of:
    emptied on both sides of the test."""
    from analytics_zoo_tpu.ops import flash_attention as fa

    def empty():
        for table in (fa.strips, fa.launch_table, fa.tile_pairs):
            table.cache_clear()
    empty()
    monkeypatch.setattr(fa, "STRIP", 128)
    yield
    monkeypatch.undo()
    empty()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("head_dim", [128, 64])
@pytest.mark.parametrize("masking", list(STRIP_MASKS))
def test_diagonal_tiles_compute_their_live_strips(orca_ctx, monkeypatch,
                                                  strips_of_128, masking,
                                                  head_dim, dtype):
    """The real kernel bodies, interpreted, where the mask leaves a
    diagonal tile dead strips: output, logsumexp, ``dq``, ``dk``, ``dv``
    (the logsumexp's cotangent included) against dense masked attention
    on the same rounded inputs, with 4 query heads over one key/value
    head in both operand layouts. The launches take ``tile_table``'s grid
    and code each such tile by its strip pattern, and say they compute
    fewer pairs than whole tiles; where no tile has a dead strip the
    launches are the whole-tile launches."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.ops import flash_attention as fa

    monkeypatch.setenv("ZOO_PALLAS_INTERPRET", "1")
    sq, sk, causal, bd, blocks, dead_strips = STRIP_MASKS[masking]
    sq, sk, block = (int(n * fa.STRIP) for n in (sq, sk, blocks))
    mask = fa.BlockDiffusionMask(bd[0] * fa.STRIP, 4, noisy=bd[1]) \
        if bd else None
    b, h, kv_heads, d = 1, 4, 1, head_dim
    rng = np.random.default_rng(sq + sk + head_dim)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)

    q, g = draw(b, sq, h, d), draw(b, sq, h, d)
    k, v = draw(b, sk, kv_heads, d), draw(b, sk, kv_heads, d)
    g_lse = jnp.asarray(rng.normal(size=(b * h, sq)), jnp.float32)
    static = fa.static_mask(causal, mask, sq, sk)
    allowed = jnp.ones((sq, sk), bool) if static is None \
        else static.dense(sq, sk)

    def flash(q, k, v):
        return fa.flash_attention_with_lse(q, k, v, causal, block, block,
                                           mask)

    got, vjp = jax.vjp(flash, q, k, v)
    dq, dk, dv = vjp((g, g_lse))
    wide = [a.astype(jnp.float32) for a in (q, k, v, g)]
    want, ref_vjp = jax.vjp(
        lambda q, k, v: _dense_with_lse(q, k, v, allowed),
        wide[0], *fa.repeat_kv_heads(*wide[:3]))
    want_dq, want_dk, want_dv = ref_vjp((wide[3], g_lse))
    want_dk, want_dv = (a.reshape(b, sk, kv_heads, h, d).sum(3)
                        for a in (want_dk, want_dv))
    for name, a, w in zip(("out", "lse", "dq", "dk", "dv"),
                          (*got, dq, dk, dv),
                          (*want, want_dq, want_dk, want_dv)):
        a = np.asarray(a, np.float32)
        if dtype == "float32":
            np.testing.assert_allclose(a, w, rtol=2e-4, atol=2e-5,
                                       err_msg=name)
        else:
            assert np.linalg.norm(a - w) < 1e-2 * np.linalg.norm(w), name

    _, _, _, bq_p, bk_p, sq_p, sk_p, _ = fa._pad_blocks(
        q[..., :1], k[..., :1], v[..., :1], block, block)
    tiles = (sq_p // bq_p, sk_p // bk_p, bq_p, bk_p, static,
             sk if sk_p != sk else None)
    launches, _ = _launches(
        lambda q, k, v: jax.vjp(flash, q, k, v)[1]((g, g_lse)), q, k, v)
    assert len(launches) == 3
    for key_major, said in zip((False, False, True), launches):
        table = fa.tile_table(*tiles, key_major, h if key_major else 1)
        launch, bodies = fa.launch_table(*tiles, key_major,
                                         h if key_major else 1)
        assert (np.delete(launch, 2, 1) == np.delete(table, 2, 1)).all()
        assert bool((launch[:, 2] >= fa.STRIPS).any()) == dead_strips
        whole = int((table[:, 2] != fa.DEAD).sum()) * bq_p * bk_p
        computed = fa.tile_pairs(*tiles, key_major, h if key_major else 1)[0]
        assert (computed < whole) == dead_strips
        assert said["pairs"] == str(computed * b
                                    * (kv_heads if key_major else h))


#: id -> (sq, sk, block_q, block_k, the mask's constructor and arguments)
GROUP_TABLES = {
    "causal_4x4": (512, 512, 128, 128, ("CausalMask", 0)),
    "causal_unequal_blocks": (512, 512, 256, 128, ("CausalMask", 0)),
    "more_keys_than_queries": (256, 512, 128, 128, ("CausalMask", 256)),
    # bottom-right aligned at fewer keys than queries shifted the other
    # way: the last two key blocks hold no allowed pair
    "key_blocks_no_query_sees": (128, 384, 128, 128, ("CausalMask", -256)),
    "block_diffusion": (256, 256, 64, 64, ("BlockDiffusionMask", 128, 4)),
    "not_causal_ragged_keys": (256, 300, 128, 128, None),
}


@pytest.mark.parametrize("groups", [2, 4, 8])
@pytest.mark.parametrize("case", list(GROUP_TABLES))
def test_key_major_table_keeps_a_key_block_over_its_groups_heads(case,
                                                                groups):
    """Pure Python. A key/value head's ``dk/dv`` table: every ``(head of
    the group, qi, ki)`` live triple once; a key block's run unbroken
    across the group's heads, each head over the block's live query
    blocks ascending; the kernels' ``first`` / ``last`` (a change of
    ``ki``) true exactly at the run's ends; with one head a group the
    table IS the parent's, bytes and shape."""
    from analytics_zoo_tpu.ops import flash_attention as fa

    sq, sk, bq, bk, masking = GROUP_TABLES[case]
    mask = getattr(fa, masking[0])(*masking[1:]) if masking else None
    nq, nk = -(-sq // bq), -(-sk // bk)
    kv_len = sk if sk % bk else None
    one = fa.tile_table(nq, nk, bq, bk, mask, kv_len, key_major=True)
    same = fa.tile_table(nq, nk, bq, bk, mask, kv_len, key_major=True,
                         groups=1)
    assert same.shape == one.shape and same.tobytes() == one.tobytes()
    table = fa.tile_table(nq, nk, bq, bk, mask, kv_len, key_major=True,
                          groups=groups)
    assert table.dtype == np.int32 and table.shape[1] == 4
    rows = [tuple(r) for r in table.tolist()]
    live = [r for r in one.tolist() if r[2] != fa.DEAD]
    # every live triple of every head of the group, once
    assert sorted(r for r in rows if r[2] != fa.DEAD) == sorted(
        (qi, ki, kind, head) for qi, ki, kind in live
        for head in range(groups))
    # key block -> head of the group -> live query blocks ascending
    assert rows == sorted(rows, key=lambda r: (r[1], r[3], r[0]))
    # a key block no query sees keeps ONE dead step, its zeros' flush
    for qi, ki, kind in one.tolist():
        if kind == fa.DEAD:
            assert [r for r in rows if r[1] == ki] == [(qi, ki, kind, 0)]
    # ``_step``'s first / last: exactly the ends of a key block's run
    ki = table[:, 1]
    first = np.r_[True, ki[1:] != ki[:-1]]
    last = np.r_[ki[1:] != ki[:-1], True]
    assert first.sum() == last.sum() == nk
    for block in range(nk):
        at = np.flatnonzero(ki == block)
        assert at.tolist() == list(range(at[0], at[-1] + 1))
        assert first[at[0]] and last[at[-1]]
        assert not first[at[1:]].any() and not last[at[:-1]].any()
    with pytest.raises(ValueError, match="key-major"):
        fa.tile_table(nq, nk, bq, bk, mask, kv_len, groups=groups)
