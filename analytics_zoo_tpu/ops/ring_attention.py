"""Ring attention — sequence/context parallelism over the ``seq`` mesh axis.

New capability vs the reference (SURVEY.md §2.6/§5: no sequence parallelism
exists anywhere in Analytics Zoo). Design: q/k/v are sharded on the sequence
dim over the ``seq`` axis; each device computes blockwise attention against
its resident k/v block while ``ppermute`` rotates k/v around the ICI ring —
after ``seq`` steps every query block has seen every key block, with O(s/p)
memory per device and compute/communication overlap left to XLA's scheduler
(the ring pattern is exactly "How to Scale Your Model"'s all-gather-free
attention recipe).

Causality is handled per ring step by comparing global block indices: a key
block strictly in the future contributes nothing; the diagonal block applies
the triangular mask.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from analytics_zoo_tpu.parallel import mesh as mesh_lib

NEG_INF = -1e30


def _ring_flash_local(q, k, v, *, axis_name: str, causal: bool,
                      block: int, n_shards: int):
    """Flash-kernel ring step: each resident k/v block goes through the
    pallas kernel (``flash_attention_with_lse``) and the per-step partial
    softmaxes merge via their logsumexps — no [s_loc, s_loc] score matrix
    ever materializes, on top of the ring's O(s/p) sharding. Causality by
    block position: past blocks run the un-masked kernel, the diagonal
    block the causal kernel, future blocks are skipped.

    ``n_shards`` is the ring size, threaded from the caller's mesh
    (``jax.lax.axis_size`` only exists on newer jax)."""
    from analytics_zoo_tpu.ops.flash_attention import (
        flash_attention_with_lse,
    )
    p = n_shards
    my = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape

    def flash_step(k_cur, v_cur, caus):
        o_i, lse_i = flash_attention_with_lse(
            q, k_cur, v_cur, caus, block, block)
        return (o_i.astype(jnp.float32).transpose(0, 2, 1, 3),
                lse_i.reshape(b, h, s_loc))

    def step_outputs(src, k_cur, v_cur):
        if not causal:
            return flash_step(k_cur, v_cur, False)
        dead = (jnp.zeros((b, h, s_loc, d), jnp.float32),
                jnp.full((b, h, s_loc), NEG_INF, jnp.float32))
        return jax.lax.cond(
            src > my, lambda: dead,
            lambda: jax.lax.cond(
                src == my,
                lambda: flash_step(k_cur, v_cur, True),
                lambda: flash_step(k_cur, v_cur, False)))

    def accum(i, num, m, den, k_cur, v_cur):
        src = (my - i) % p
        o_i, lse_i = step_outputs(src, k_cur, v_cur)
        m_new = jnp.maximum(m, lse_i)
        c_old = jnp.exp(m - m_new)
        c_new = jnp.exp(lse_i - m_new)
        num = num * c_old[..., None] + o_i * c_new[..., None]
        den = den * c_old + c_new
        return num, m_new, den

    def body(i, carry):
        num, m, den, k_cur, v_cur = carry
        num, m, den = accum(i, num, m, den, k_cur, v_cur)
        perm = [(r, (r + 1) % p) for r in range(p)]
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return num, m, den, k_next, v_next

    num0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
    den0 = jnp.zeros((b, h, s_loc), jnp.float32)
    num, m, den, k_last, v_last = jax.lax.fori_loop(
        0, p - 1, body, (num0, m0, den0, k, v))
    num, m, den = accum(p - 1, num, m, den, k_last, v_last)
    out = num / jnp.maximum(den, 1e-37)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool,
                          n_shards: int):
    """Runs inside shard_map: q,k,v are the local [b, s_loc, h, d] blocks."""
    p = n_shards
    my = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def accum(i, o, m, l, k_cur, v_cur):
        # global index of the key block currently resident here
        src = (my - i) % p
        s = jnp.einsum("bqhd,bkhd->bhqk", qf,
                       k_cur.astype(jnp.float32)) * scale
        if causal:
            q_pos = my * s_loc + jnp.arange(s_loc)
            k_pos = src * s_loc + jnp.arange(s_loc)
            allowed = k_pos[None, :] <= q_pos[:, None]
            s = jnp.where(allowed[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        pr = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + pr.sum(-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", pr, v_cur.astype(jnp.float32))
        return o_new, m_new, l_new

    def body(i, carry):
        o, m, l, k_cur, v_cur = carry
        o, m, l = accum(i, o, m, l, k_cur, v_cur)
        # rotate k/v one step around the ring (lax.ppermute over ICI)
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return o, m, l, k_next, v_next

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    # p-1 rotations; the block resident after the last rotation is consumed
    # by a final accum outside the loop so no ppermute result is discarded
    o, m, l, k_last, v_last = jax.lax.fori_loop(
        0, p - 1, body, (o0, m0, l0, k, v))
    o, m, l = accum(p - 1, o, m, l, k_last, v_last)
    out = o / jnp.maximum(l, 1e-37)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention(q, k, v, mesh=None, axis_name: str = mesh_lib.SEQ_AXIS,
                   causal: bool = False, batch_axis: Optional[str] = None,
                   use_flash: Optional[bool] = None,
                   flash_block: int = 128):
    """q,k,v: [batch, seq, heads, dim] global arrays (seq sharded over
    ``axis_name``) → same-shaped output, seq-sharded.

    ``batch_axis``: optionally also shard batch (e.g. "data") so the same
    call works under dp×sp meshes.

    ``use_flash``: run each resident block through the pallas flash
    kernels and merge ring steps via logsumexp — O(block) memory inside
    each step on top of the ring's O(s/p). ``None`` auto-selects on TPU
    whenever the local block spans at least one flash tile
    (``default_use_flash``). The kernels pad internally now —
    ``head_dim % 128 != 0`` (e.g. 64, the BERT class) packs into the 128
    lane and ragged local blocks get a masked tail tile — so neither
    disqualifies a shape anymore; the remaining blockwise fallbacks are
    economic (tiny local blocks), not correctness limits.
    """
    if mesh is None:
        mesh = mesh_lib.get_default_mesh()
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assert axis_name in axes, f"mesh has no {axis_name!r} axis: {axes}"
    p = axes[axis_name]
    assert q.shape[1] % p == 0, \
        f"seq len {q.shape[1]} must divide over {axis_name}={p}"
    s_loc, d = q.shape[1] // p, q.shape[-1]
    if use_flash is None:
        from analytics_zoo_tpu.ops.flash_attention import default_use_flash
        use_flash = default_use_flash(s_loc, d, flash_block)
    spec = P(batch_axis, axis_name, None, None)
    if use_flash:
        # ragged local blocks are fine: the kernel pads the tail k-block
        # and masks padded key positions to −∞ (flash_attention.py)
        fn = functools.partial(_ring_flash_local, axis_name=axis_name,
                               causal=causal, block=flash_block,
                               n_shards=p)
    else:
        fn = functools.partial(_ring_attention_local, axis_name=axis_name,
                               causal=causal, n_shards=p)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
