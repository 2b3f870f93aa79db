"""Pipeline parallelism — GPipe schedule over the ``pipe`` mesh axis.

NEW capability vs the reference (SURVEY.md §2.6: "TP / PP / SP / EP / CP —
absent in reference"; its only parallelism is per-core data parallel,
Topology.scala:1145-1550). The TPU idiom: identical pipeline stages hold
their parameters stacked on a leading stage dimension that is sharded over
the ``pipe`` axis; inside ``shard_map`` each device runs its stage and
hands activations to the next device with ``lax.ppermute`` over ICI, while
``lax.scan`` drives the microbatch schedule. Total ticks =
n_micro + n_stages - 1 (the GPipe bubble); grads flow through ppermute, so
the same ``jax.grad`` training path works unchanged.

Heterogeneous prologue/epilogue (embedding, head) stay outside the
pipelined region — they run data-parallel as usual.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import numpy as np

from analytics_zoo_tpu.parallel import mesh as mesh_lib


def _batch_layout(mesh, axis, batch: int, n_microbatches: int):
    """(pipe size S, dp size, batch spec axis, microbatch rows mb); raises
    when the batch does not divide over microbatches × dp."""
    S = mesh_lib.mesh_axis_size(mesh, axis)
    dp = mesh_lib.mesh_axis_size(mesh, mesh_lib.DATA_AXIS)
    batch_axis = mesh_lib.DATA_AXIS if dp > 1 else None
    M = int(n_microbatches)
    if batch % (M * max(dp, 1)):
        raise ValueError(f"batch {batch} not divisible by n_microbatches "
                         f"{M} x dp {dp}")
    return S, dp, batch_axis, batch // M // max(dp, 1)


def stack_stage_params(params_list):
    """Stack S per-stage pytrees (identical structure) along a new leading
    stage axis — the layout ``gpipe`` expects (shard dim 0 over ``pipe``)."""
    import jax
    return jax.tree_util.tree_map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *params_list)


def gpipe(stage_fn: Callable, stacked_params, x, *, mesh=None,
          n_microbatches: int, axis: str = mesh_lib.PIPE_AXIS):
    """Run ``x`` through S pipeline stages with the GPipe schedule.

    - ``stage_fn(stage_params, activation) -> activation`` — one stage;
      activations must keep one shape across stages.
    - ``stacked_params``: pytree whose leaves have leading dim S
      (``stack_stage_params``), sharded over ``axis``.
    - ``x``: [batch, ...]; batch must divide into ``n_microbatches``.

    Returns [batch, ...] outputs, replicated over the pipe axis. Jittable
    and differentiable (use under ``jax.grad`` for training).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    shard_map = partial(jax.shard_map, check_vma=False)
    if mesh is None:
        mesh = mesh_lib.get_default_mesh()
    if mesh_lib.mesh_axis_size(mesh, axis) < 2:
        raise ValueError(f"mesh has no usable {axis!r} axis: "
                         f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
    # split the batch over the data axis (when present) so each dp group
    # pipelines only its own slice — P() here would all-gather the global
    # batch and make every dp replica redundantly run all microbatches
    S, dp, batch_spec_axis, mb = _batch_layout(mesh, axis, x.shape[0],
                                               n_microbatches)
    M = int(n_microbatches)

    first = jax.tree_util.tree_leaves(stacked_params)[0]
    if first.shape[0] != S:
        raise ValueError(
            f"stacked params leading dim {first.shape[0]} != pipe size {S}")

    params_spec = jax.tree_util.tree_map(
        lambda _: P(axis), stacked_params)
    x_spec = P(batch_spec_axis)

    @partial(shard_map, mesh=mesh, in_specs=(params_spec, x_spec),
             out_specs=x_spec)
    def run(p_stage, x_all):
        # p_stage leaves: [1, ...] (this device's stage) — drop the dim.
        # x_all: this dp group's batch slice [b/dp, ...]
        p_stage = jax.tree_util.tree_map(lambda a: a[0], p_stage)
        idx = jax.lax.axis_index(axis)
        micro = x_all.reshape((M, mb) + x_all.shape[1:])
        out_buf = jnp.zeros((M, mb) + x_all.shape[1:], x_all.dtype)
        carry0 = jnp.zeros((mb,) + x_all.shape[1:], x_all.dtype)

        def tick(state, t):
            carry, out_buf = state
            # stage 0 ingests microbatch t (clamped; masked later)
            feed = micro[jnp.minimum(t, M - 1)]
            inp = jnp.where(idx == 0, feed, carry)
            out = stage_fn(p_stage, inp)
            # last stage writes its result for microbatch t-(S-1)
            slot = jnp.clip(t - (S - 1), 0, M - 1)
            valid = jnp.logical_and(idx == S - 1, t >= S - 1)
            upd = jnp.where(valid, out, out_buf[slot])
            out_buf = jax.lax.dynamic_update_index_in_dim(out_buf, upd,
                                                          slot, 0)
            # hand activations down the pipe: i -> i+1 (ring; stage 0
            # ignores what it receives from S-1)
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % S) for i in range(S)])
            return (nxt, out_buf), None

        (_, out_buf), _ = jax.lax.scan(
            tick, (carry0, out_buf), jnp.arange(M + S - 1))
        # result lives on the last stage; replicate over the pipe axis
        out_buf = jnp.where(idx == S - 1, out_buf, 0.0)
        out_buf = jax.lax.psum(out_buf, axis)
        return out_buf.reshape((x_all.shape[0],) + x_all.shape[1:])

    return run(stacked_params, x)


def pack_stage_params(params_list):
    """Pack S per-stage pytrees of DIFFERENT structures into one
    ``[S, maxlen]`` float array (rows zero-padded) + the per-stage unravel
    functions. The packed array shards row-wise over ``pipe`` — that is
    how heterogeneous stages (embedding / block / head) become one SPMD
    tensor."""
    import jax
    from jax.flatten_util import ravel_pytree

    flats, unravels, sizes = [], [], []
    for p in params_list:
        flat, unravel = ravel_pytree(p)
        flats.append(np.asarray(flat, np.float32))
        unravels.append(unravel)
        sizes.append(flat.size)
    maxlen = max(sizes)
    packed = np.stack([np.pad(f, (0, maxlen - f.size)) for f in flats])
    return packed, unravels, sizes


def gpipe_hetero(stage_fns, unravels, sizes, packed, feed, *, mesh=None,
                 n_microbatches: int, act_shape, out_shape,
                 act_dtype=None, out_dtype=None,
                 axis: str = mesh_lib.PIPE_AXIS):
    """GPipe over HETEROGENEOUS stages (embedding → blocks → head all
    inside the schedule).

    SPMD trick: every device runs the same program; ``lax.switch`` on the
    device's stage index selects its branch, which slices+unravels its row
    of ``packed`` into that stage's real param pytree and applies its own
    computation. Contract for ``stage_fns[s](params_s, act, feed_mb)``:
    returns ``(act_out, final_out)`` where ``act_out`` has per-microbatch
    shape ``(mb,) + act_shape`` for EVERY stage (the ppermute carry) and
    ``final_out`` has ``(mb,) + out_shape`` (zeros except on the last
    stage). ``feed``: the raw per-example model input (e.g. token ids),
    consumed by stage 0.

    Differentiable in ``packed`` — the whole pipeline trains through the
    standard Estimator with a ``pipe``-sharded parameter row per device.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    smap = partial(jax.shard_map, check_vma=False)
    if mesh is None:
        mesh = mesh_lib.get_default_mesh()
    if mesh_lib.mesh_axis_size(mesh, axis) != len(stage_fns):
        raise ValueError(f"{len(stage_fns)} stages but pipe axis size "
                         f"{mesh_lib.mesh_axis_size(mesh, axis)}")
    S, dp, batch_axis, mb = _batch_layout(mesh, axis, feed.shape[0],
                                          n_microbatches)
    M = int(n_microbatches)
    act_dtype = act_dtype or jnp.float32
    out_dtype = out_dtype or jnp.float32

    def make_branch(s):
        def branch(vec, act, tok):
            p = unravels[s](vec[:sizes[s]])
            return stage_fns[s](p, act, tok)
        return branch

    branches = [make_branch(s) for s in range(S)]

    @partial(smap, mesh=mesh, in_specs=(P(axis), P(batch_axis)),
             out_specs=P(batch_axis))
    def run(p_rows, feed_all):
        vec = p_rows[0]                       # this device's stage row
        idx = jax.lax.axis_index(axis)
        micro = feed_all.reshape((M, mb) + feed_all.shape[1:])
        carry0 = jnp.zeros((mb,) + tuple(act_shape), act_dtype)
        out_buf = jnp.zeros((M, mb) + tuple(out_shape), out_dtype)

        def tick(state, t):
            carry, out_buf = state
            tok = micro[jnp.minimum(t, M - 1)]
            act_out, fin = jax.lax.switch(idx, branches, vec, carry, tok)
            slot = jnp.clip(t - (S - 1), 0, M - 1)
            valid = jnp.logical_and(idx == S - 1, t >= S - 1)
            upd = jnp.where(valid, fin, out_buf[slot])
            out_buf = jax.lax.dynamic_update_index_in_dim(out_buf, upd,
                                                          slot, 0)
            nxt = jax.lax.ppermute(
                act_out, axis, [(i, (i + 1) % S) for i in range(S)])
            return (nxt, out_buf), None

        (_, out_buf), _ = jax.lax.scan(
            tick, (carry0, out_buf), jnp.arange(M + S - 1))
        out_buf = jnp.where(idx == S - 1, out_buf, 0.0)
        out_buf = jax.lax.psum(out_buf, axis)
        return out_buf.reshape((feed_all.shape[0],) + tuple(out_shape))

    return run(packed, feed)


def _ln(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    import jax
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _block_apply(p, h, nh):
    """Pre-LN causal transformer block on [mb, L, D] (plain-pytree params:
    the pipelined region cannot use flax modules — stage params are
    unraveled from the packed row). ``nh``: static head count."""
    import jax
    import jax.numpy as jnp

    D = h.shape[-1]
    x = _ln(h, p["ln1_g"], p["ln1_b"])
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    L = h.shape[1]
    hd = D // nh
    def split(a):
        return a.reshape(a.shape[0], L, nh, hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", split(q), split(k)) / np.sqrt(hd)
    cmask = jnp.tril(jnp.ones((L, L), bool))
    scores = jnp.where(cmask, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, split(v))
    h = h + attn.reshape(h.shape[0], L, D) @ p["wo"]
    x = _ln(h, p["ln2_g"], p["ln2_b"])
    h = h + jax.nn.gelu(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
    return h


class PipelinedTransformerLM:
    """Causal transformer LM whose embedding, blocks AND head all live
    inside the gpipe schedule (heterogeneous stages): stage 0 =
    embedding + block, middle stages = block, last stage = block + LM
    head. Plugs into ``Estimator.from_fn`` for dp×pp training; the single
    trainable leaf is the pipe-sharded packed parameter matrix.

    ``apply_sequential`` runs the identical stages without the pipeline —
    the correctness oracle the tests compare against."""

    def __init__(self, vocab: int, d_model: int = 32, n_heads: int = 4,
                 d_ff: int = 64, seq_len: int = 16, n_stages: int = 4,
                 n_microbatches: int = 4, mesh=None):
        self.vocab, self.D, self.nh = vocab, d_model, n_heads
        self.d_ff, self.L = d_ff, seq_len
        self.S, self.M = n_stages, n_microbatches
        self.mesh = mesh
        self._unravels = None
        self._sizes = None

    # ---- per-stage param construction ----
    def _block_params(self, rng):
        import jax
        D, F = self.D, self.d_ff
        ks = jax.random.split(rng, 6)
        s = 1.0 / np.sqrt(D)
        return {
            "ln1_g": np.ones((D,), np.float32),
            "ln1_b": np.zeros((D,), np.float32),
            "ln2_g": np.ones((D,), np.float32),
            "ln2_b": np.zeros((D,), np.float32),
            "wq": np.asarray(jax.random.normal(ks[0], (D, D))) * s,
            "wk": np.asarray(jax.random.normal(ks[1], (D, D))) * s,
            "wv": np.asarray(jax.random.normal(ks[2], (D, D))) * s,
            "wo": np.asarray(jax.random.normal(ks[3], (D, D))) * s,
            "w1": np.asarray(jax.random.normal(ks[4], (D, F))) * s,
            "b1": np.zeros((F,), np.float32),
            "w2": np.asarray(jax.random.normal(ks[5], (F, D)))
            / np.sqrt(F),
            "b2": np.zeros((D,), np.float32),
        }

    def _stage_param_list(self, rng):
        import jax
        keys = jax.random.split(rng, self.S + 3)
        stages = []
        for s in range(self.S):
            p = {"block": self._block_params(keys[s])}
            if s == 0:
                p["emb"] = np.asarray(jax.random.normal(
                    keys[-3], (self.vocab, self.D))) * 0.02
                p["pos"] = np.asarray(jax.random.normal(
                    keys[-2], (self.L, self.D))) * 0.02
            if s == self.S - 1:
                p["head"] = np.asarray(jax.random.normal(
                    keys[-1], (self.D, self.vocab))) / np.sqrt(self.D)
            stages.append(p)
        return stages

    # ---- stage functions (gpipe_hetero contract) ----
    def _stage_fns(self):
        import jax.numpy as jnp
        V, L, D, nh = self.vocab, self.L, self.D, self.nh

        def first(p, act, tok):
            h = p["emb"][tok.astype(jnp.int32)] + p["pos"][None, :, :]
            h = _block_apply(p["block"], h, nh)
            return h, jnp.zeros((tok.shape[0], L, V), jnp.float32)

        def mid(p, act, tok):
            h = _block_apply(p["block"], act, nh)
            return h, jnp.zeros((act.shape[0], L, V), jnp.float32)

        def last(p, act, tok):
            h = _block_apply(p["block"], act, nh)
            return h, _ln(h, jnp.ones((D,)), jnp.zeros((D,))) @ p["head"]

        return [first] + [mid] * (self.S - 2) + [last]

    # ---- Estimator.from_fn surface ----
    def init(self, rng, tokens):
        packed, unravels, sizes = pack_stage_params(
            self._stage_param_list(rng))
        self._unravels, self._sizes = unravels, sizes
        return {"pipe": packed}

    def apply(self, params, tokens):
        assert self._unravels is not None, "init first"
        return gpipe_hetero(
            self._stage_fns(), self._unravels, self._sizes,
            params["pipe"], tokens, mesh=self.mesh,
            n_microbatches=self.M, act_shape=(self.L, self.D),
            out_shape=(self.L, self.vocab))

    def apply_sequential(self, params, tokens):
        """Same stages, no pipeline — the correctness oracle."""
        import jax.numpy as jnp
        fns = self._stage_fns()
        act = jnp.zeros((tokens.shape[0], self.L, self.D))
        out = None
        for s, fn in enumerate(fns):
            vec = params["pipe"][s][:self._sizes[s]]
            act, out = fn(self._unravels[s](vec), act, tokens)
        return out

    def param_rules(self):
        return [(r"pipe", (mesh_lib.PIPE_AXIS,))]


class PipelinedMLP:
    """Convenience model: S identical Dense+activation stages pipelined
    over the pipe axis; prologue/epilogue dense layers replicated.

    Exposes ``init(rng, x)`` / ``apply(params, x)`` so it plugs into
    ``Estimator.from_fn`` — pipeline-parallel training through the standard
    engine."""

    def __init__(self, hidden: int, out_dim: int, n_stages: int,
                 n_microbatches: int = 4, mesh=None):
        self.hidden, self.out_dim = hidden, out_dim
        self.S, self.M = n_stages, n_microbatches
        self.mesh = mesh

    def init(self, rng, x):
        import jax
        k_in, k_stage, k_out = jax.random.split(rng, 3)
        f_in = x.shape[-1]
        scale = 1.0 / np.sqrt(self.hidden)
        return {
            "w_in": jax.random.normal(k_in, (f_in, self.hidden)) / np.sqrt(f_in),
            "stages": {
                "w": jax.random.normal(
                    k_stage, (self.S, self.hidden, self.hidden)) * scale,
                "b": np.zeros((self.S, self.hidden), np.float32),
            },
            "w_out": jax.random.normal(k_out, (self.hidden, self.out_dim))
            * scale,
        }

    def apply(self, params, x):
        import jax.numpy as jnp

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        h = x @ params["w_in"]
        h = gpipe(stage_fn, params["stages"], h, mesh=self.mesh,
                  n_microbatches=self.M)
        return h @ params["w_out"]

    def param_rules(self):
        """Shard the stacked stage dim over ``pipe`` for the Estimator."""
        return [(r"stages/(w|b)", (mesh_lib.PIPE_AXIS,))]
