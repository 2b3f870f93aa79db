"""Ulysses attention — all-to-all sequence parallelism over ``seq``.

New capability vs the reference (SURVEY.md §5: "context parallelism and
Ulysses-style head/sequence all-to-all via shard_map over the ICI mesh" —
nothing of the kind exists in Analytics Zoo). The DeepSpeed-Ulysses
recipe: activations arrive sequence-sharded ``[b, s/p, h, d]``; ONE
all-to-all reshards them to head-sharded ``[b, s, h/p, d]`` so every
device runs ordinary FULL attention over its own heads; a second
all-to-all brings the outputs back to sequence sharding. Communication is
two all-to-alls of the activation size — cheaper than ring attention's p
ppermute rounds when the head count divides the mesh axis, while ring wins
when s is huge and heads are few; both ride the same ``seq`` axis so
callers can pick per-model.

Complementary pair: ``ring_attention`` (ops/ring_attention.py) keeps k/v
moving, Ulysses keeps data resident and moves responsibility (heads).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from analytics_zoo_tpu.parallel import mesh as mesh_lib


def _attention(q, k, v, causal: bool):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d).astype(q.dtype)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, jnp.finfo(s.dtype).min)
    probs = jax.nn.softmax(s.astype(jnp.float32), -1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def ulysses_attention(q, k, v, *, mesh=None, causal: bool = False,
                      axis: str = mesh_lib.SEQ_AXIS,
                      batch_axis: Optional[str] = None,
                      use_flash: Optional[bool] = None):
    """q, k, v: [b, s, h, d] GLOBAL arrays sequence-sharded over ``axis``
    (s divisible by the axis size, h divisible too; ``batch_axis`` names
    the data-parallel axis the batch dim is sharded over, if any). Returns
    [b, s, h, d] with the same sharding.

    Inside shard_map: all-to-all seq→head, full attention on local heads,
    all-to-all head→seq. XLA lowers both to one ICI all-to-all each.

    ``use_flash``: run the per-device full attention through the pallas
    flash kernels (fwd + FA-2 bwd) instead of materializing the [s, s]
    score matrix — after the all-to-all each device holds the FULL
    sequence for its heads, so long-context Ulysses without flash is
    O(s²) HBM per device. ``None`` auto-selects on TPU whenever the
    sequence spans at least one flash tile (``default_use_flash``). The
    kernels pad internally now — ``head_dim % 128 != 0`` (e.g. 64, the
    BERT class) packs into the 128 lane and ragged sequences get a
    masked tail tile — so neither disqualifies a shape anymore.
    """
    if mesh is None:
        mesh = mesh_lib.get_default_mesh()
    p = mesh_lib.mesh_axis_size(mesh, axis)
    if p < 2:
        raise ValueError(f"mesh has no usable {axis!r} axis: "
                         f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
    b, s, h, d = q.shape
    if s % p or h % p:
        raise ValueError(f"seq {s} and heads {h} must divide the {axis!r} "
                         f"axis size {p}")
    if use_flash is None:
        from analytics_zoo_tpu.ops.flash_attention import default_use_flash
        use_flash = default_use_flash(s, d)

    spec = P(batch_axis, axis, None, None)
    smap = partial(jax.shard_map, check_vma=False)

    @partial(smap, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    def run(q_loc, k_loc, v_loc):
        # [b, s/p, h, d] → all-to-all → [b, s, h/p, d]: split the head dim
        # across devices, concatenate the sequence dim
        def to_heads(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        def to_seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        qh, kh, vh = to_heads(q_loc), to_heads(k_loc), to_heads(v_loc)
        if use_flash:
            from analytics_zoo_tpu.ops.flash_attention import (
                flash_attention,
            )
            out = flash_attention(qh, kh, vh, causal)
        else:
            out = _attention(qh, kh, vh, causal)
        return to_seq(out)

    return run(q, k, v)
