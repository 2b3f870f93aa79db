"""Mixture-of-Experts with expert parallelism.

NEW capability vs the reference (SURVEY.md §2.6: EP absent). GShard/Switch
style: top-k softmax gating with a fixed capacity per expert, dispatch and
combine as one-hot einsum contractions, experts as weight tensors stacked
on a leading E dim. Sharding the E dim over the ``expert`` mesh axis makes
XLA emit the token all-to-alls over ICI — no hand-written routing layer
(the design the scaling-book recipe prescribes: annotate, let XLA insert
collectives).

``MoEModule`` is a flax module usable anywhere (e.g. as a transformer FFN
replacement); ``ep_param_rules()`` gives the Estimator partition rules.
Auxiliary load-balancing loss (Switch §2.2 style) is returned via the
module's ``aux_loss`` attribute collection.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops import moe_combine
from analytics_zoo_tpu.parallel import mesh as mesh_lib


def top_k_gating(logits: jnp.ndarray, k: int, capacity: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """logits: [N, E] → (dispatch [N, E, C] one-hot, combine [N, E, C]
    weights, aux load-balance loss). Tokens beyond an expert's capacity C
    are dropped (their combine weight is 0) — the standard fixed-shape
    trade that keeps everything jittable."""
    N, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)

    # Switch aux loss: E * sum_e (fraction of tokens routed to e *
    # mean gate prob of e)
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(jax.nn.one_hot(top1, E), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)

    dispatch = jnp.zeros((N, E, capacity), logits.dtype)
    combine = jnp.zeros((N, E, capacity), logits.dtype)
    residual_probs = probs
    filled = jnp.zeros((E,), logits.dtype)  # slots used by earlier passes
    for _ in range(k):
        choice = jnp.argmax(residual_probs, axis=-1)            # [N]
        gate = jnp.take_along_axis(residual_probs, choice[:, None],
                                   axis=-1)[:, 0]               # [N]
        onehot = jax.nn.one_hot(choice, E, dtype=logits.dtype)  # [N, E]
        # position within the expert's queue, offset by slots already
        # consumed in earlier passes (otherwise 1st- and 2nd-choice tokens
        # of the same expert would share a slot and their features sum)
        pos = (jnp.cumsum(onehot, axis=0) - 1.0 + filled[None, :]) * onehot
        in_cap = (pos < capacity) & (onehot > 0)
        pos_idx = jnp.clip(pos.astype(jnp.int32), 0, capacity - 1)
        slot = jax.nn.one_hot(pos_idx, capacity, dtype=logits.dtype)
        contrib = jnp.where(in_cap[..., None], slot, 0.0)       # [N, E, C]
        dispatch = dispatch + contrib
        combine = combine + contrib * gate[:, None, None]
        filled = filled + jnp.sum(onehot * in_cap, axis=0)
        residual_probs = residual_probs * (1.0 - onehot)
    return dispatch, combine, aux


class MoEModule(nn.Module):
    """Expert-parallel FFN block: ``y = combine @ FFN_e(dispatch @ x)``.

    Input [..., d_model] → output [..., d_model]. Expert weights have
    leading dim ``n_experts``; shard it over the ``expert`` axis
    (``ep_param_rules``) for expert parallelism.
    """

    n_experts: int
    d_model: int
    d_hidden: int
    k: int = 2
    capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x, train: bool = False):
        orig_shape = x.shape
        tokens = x.reshape(-1, self.d_model)                    # [N, d]
        N = tokens.shape[0]
        capacity = max(1, int(self.capacity_factor * N *
                              self.k / self.n_experts))

        gate_w = self.param(
            "gate", nn.initializers.lecun_normal(),
            (self.d_model, self.n_experts))
        dispatch, combine, aux = top_k_gating(
            tokens @ gate_w, self.k, capacity)
        self.sow("aux_loss", "load_balance", aux)

        w1 = self.param("w1", nn.initializers.lecun_normal(),
                        (self.n_experts, self.d_model, self.d_hidden))
        b1 = self.param("b1", nn.initializers.zeros,
                        (self.n_experts, self.d_hidden))
        w2 = self.param("w2", nn.initializers.lecun_normal(),
                        (self.n_experts, self.d_hidden, self.d_model))
        b2 = self.param("b2", nn.initializers.zeros,
                        (self.n_experts, self.d_model))

        # all-to-all happens here when E is sharded over 'expert'
        expert_in = jnp.einsum("nec,nd->ecd", dispatch, tokens)
        h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", expert_in, w1)
                        + b1[:, None, :])
        expert_out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
        out = jnp.einsum("nec,ecd->nd", combine, expert_out)
        return out.reshape(orig_shape)


def ep_param_rules() -> list:
    """Partition rules sharding expert-stacked weights over ``expert``."""
    ax = mesh_lib.EXPERT_AXIS
    return [
        (r"/(w1|b1|w2|b2)$", (ax,)),
    ]


# ------------------------------------------------ dropless, held experts
#
# The second expert layer of this module (ROADMAP, Design queue: the
# capacity path above goes when a four-chip expert cell exists). Routing
# drops no token; the layer is told which experts it holds, routes over
# all of them and computes its own experts' part of the result.

def sigmoid_top_k_routing(logits, bias, k: int, normalize: bool = True,
                          scale: float = 1.0):
    """``logits`` [N, E] -> (expert ids [N, k] int32, weights [N, k] f32).
    Scores are ``sigmoid(logits)``; the ``k`` experts with the largest
    ``score + bias`` are selected (the bias steers the selection only and
    carries no gradient); the weights are the scores at the selected,
    divided by their sum (+1e-6) when ``normalize``, times ``scale``."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, ids = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    return ids.astype(jnp.int32), weights * scale


def softmax_top_k_routing(logits, k: int, normalize: bool = True,
                          scale: float = 1.0):
    """``logits`` [N, E] -> (expert ids [N, k] int32, weights [N, k] f32).
    ``p = softmax(logits)`` over all ``E`` in float32; the ``k`` experts
    with the largest ``p`` are selected; the weights are ``p`` at the
    selected, divided by their sum when ``normalize``, times ``scale``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, ids = jax.lax.top_k(probs, k)
    if normalize:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return ids.astype(jnp.int32), weights * scale


#: the ways a ``Router`` scores: sigmoid scores with a selection-only
#: ``expert_bias`` leaf, or a softmax over all experts and no such leaf
ROUTER_SCORINGS = ("sigmoid_bias", "softmax")


@jax.custom_vjp
def _take_rows(x, tok, rows, inside, ends):
    """``x[tok]``: the window's rows gathered from the tokens. ``rows``
    [N, k] says where in the window each assignment of a token sits and
    ``inside`` [N, k] whether it sits there at all, so that the gradient
    is a gather too (a scatter-add over 2048-wide rows otherwise);
    ``ends`` [G] where each held expert's rows of the window end."""
    return x[tok]


def _take_rows_fwd(x, tok, rows, inside, ends):
    return x[tok], (rows, inside, ends)


def _take_rows_bwd(res, g):
    rows, inside, ends = res
    return _sum_rows(g, rows, inside, ends), None, None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _sum_rows(y, rows, inside, ends):
    """[N, H]: for every token the sum of the window's rows its
    assignments sit at (the rows sorted by held expert, ``ends``, then by
    token): pallas kernels that read those rows alone where
    ``moe_combine.engages``, else ``k`` gathers."""
    if moe_combine.engages(y):
        return moe_combine.sum_rows(y, jnp.where(inside, rows, -1), ends)
    out = 0
    for j in range(rows.shape[1]):
        out = out + jnp.where(inside[:, j, None], y[rows[:, j]], 0)
    return out


@jax.custom_vjp
def _put_rows(y, tok, rows, inside, ends):
    """The inverse of :func:`_take_rows`: ``y`` [M, H], one row per
    assignment of the window, summed into its token."""
    return _sum_rows(y, rows, inside, ends)


def _put_rows_fwd(y, tok, rows, inside, ends):
    return _sum_rows(y, rows, inside, ends), (tok,)


def _put_rows_bwd(res, g):
    (tok,) = res
    return g[tok], None, None, None, None


_put_rows.defvjp(_put_rows_fwd, _put_rows_bwd)


#: the first window of ``held_expert_ffn`` over the rows an even routing
#: gives the held experts (a balanced router's share wanders by a few
#: hundredths of the even one)
WINDOW_FACTOR = 1.25


def held_expert_ffn(x, ids, weights, w1, w3, w2, held, n_experts: int):
    """``sum_{j: ids[t,j] held} weights[t,j] * E_{ids[t,j]}(x_t)`` with
    ``E(x) = (silu(x @ w1) * (x @ w3)) @ w2``; the held experts' loads;
    and the rows of the first window, which are computed whether or not
    an assignment sits there.

    ``x`` [N, H]; ``ids``, ``weights`` [N, k] from the router over all
    ``n_experts``; ``w1``, ``w3`` [G, H, I] and ``w2`` [G, I, H], the
    weights of the G experts this layer holds, ``held`` their ids.

    The assignments are sorted by held expert (those of absent experts
    last) and the three products run as grouped matrix products
    (``jax.lax.ragged_dot``) over a window of the sorted rows. The window
    is ``WINDOW_FACTOR`` times the expected ``N * k * G / n_experts``
    rows, of a fixed size and computed whole, so the time does not follow
    the routing; whatever falls beyond it is computed in a second window
    under a ``cond``: no token is dropped however uneven the routing, and
    an even one never pays for the second window."""
    n, k = ids.shape
    g = len(held)
    nk = n * k
    slot = np.full((n_experts,), g, np.int32)
    slot[list(held)] = np.arange(g)
    group = jnp.asarray(slot)[ids].reshape(nk)          # g: not held
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    position = jnp.argsort(order).astype(jnp.int32).reshape(n, k)
    counts = jnp.sum(group[:, None] == jnp.arange(g)[None, :], axis=0,
                     dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    n_held = ends[-1]
    is_held = (group < g).reshape(n, k)
    flat_weights = weights.reshape(nk)

    def window(start: int, size: int, x, w1, w3, w2, flat_weights):
        at = order[start:start + size]                  # assignment of a row
        tok = at // k
        inside = is_held & (position >= start) & (position < start + size)
        rows = jnp.clip(position - start, 0, size - 1)
        valid = (jnp.arange(start, start + size) < n_held)[:, None]
        upto = jnp.clip(ends, start, start + size)
        sizes = upto - jnp.clip(starts, start, start + size)
        # the window's rows that hold no held assignment go to the last
        # group (their results are cleared below): the grouped products
        # skip rows outside every group, and their time would follow the
        # routing from step to step and from seed to seed
        sizes = sizes.at[-1].add(size - jnp.sum(sizes))
        # where each held expert's rows of the window end
        in_window = upto - start
        xs = _take_rows(x, tok, rows, inside, in_window)
        with jax.named_scope("products"):
            # rows past the last group hold whatever a product left there:
            # each result is cleared before anything is computed from it
            a = jnp.where(valid, jax.lax.ragged_dot(
                xs, w1.astype(xs.dtype), sizes), 0)
            b = jnp.where(valid, jax.lax.ragged_dot(
                xs, w3.astype(xs.dtype), sizes), 0)
            y = jnp.where(valid, jax.lax.ragged_dot(
                jax.nn.silu(a) * b, w2.astype(xs.dtype), sizes), 0)
        y = y * flat_weights[at][:, None].astype(y.dtype)
        return _put_rows(y, tok, rows, inside, in_window)

    main = nk if g == n_experts else min(
        nk, -(-int(WINDOW_FACTOR * nk * g / n_experts) // 512) * 512)
    operands = (x, w1, w3, w2, flat_weights)
    out = window(0, main, *operands)
    if main < nk:
        # recomputed in the backward pass: a window that seldom runs keeps
        # nothing for it
        rest = jax.checkpoint(functools.partial(window, main, nk - main))
        out = out + jax.lax.cond(n_held > main, rest,
                                 lambda *_: jnp.zeros_like(out), *operands)
    return out, counts, main


class HeldExperts(nn.Module):
    """The weights of the experts a layer holds, stacked on a leading
    dim, and their part of the layer's result."""

    held: Tuple[int, ...]
    n_experts: int
    d_hidden: int
    dtype: Optional[object] = None
    kernel_init: nn.initializers.Initializer = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x, ids, weights):
        g, d = len(self.held), x.shape[-1]
        shape_in, shape_out = (g, d, self.d_hidden), (g, self.d_hidden, d)
        w1 = self.param("w1", self.kernel_init, shape_in, jnp.float32)
        w3 = self.param("w3", self.kernel_init, shape_in, jnp.float32)
        w2 = self.param("w2", self.kernel_init, shape_out, jnp.float32)
        dtype = self.dtype or x.dtype
        return held_expert_ffn(x.astype(dtype), ids, weights, w1, w3, w2,
                               self.held, self.n_experts)


class Router(nn.Module):
    """Scores over ALL experts in float32, and the selection, by
    ``scoring`` (``ROUTER_SCORINGS``): ``"sigmoid_bias"``
    (``sigmoid_top_k_routing``, with an ``expert_bias`` leaf) or
    ``"softmax"`` (``softmax_top_k_routing``, the kernel alone)."""

    n_experts: int
    k: int
    normalize: bool = True
    scale: float = 1.0
    kernel_init: nn.initializers.Initializer = nn.initializers.lecun_normal()
    scoring: str = "sigmoid_bias"

    @nn.compact
    def __call__(self, x):
        if self.scoring not in ROUTER_SCORINGS:
            raise ValueError(f"scoring {self.scoring!r} is not one of "
                             f"{ROUTER_SCORINGS}")
        kernel = self.param("kernel", self.kernel_init,
                            (x.shape[-1], self.n_experts), jnp.float32)
        if self.scoring == "sigmoid_bias":
            bias = self.param("expert_bias", nn.initializers.zeros,
                              (self.n_experts,), jnp.float32)
        logits = jnp.dot(x.astype(jnp.float32), kernel,
                         precision=jax.lax.Precision.HIGHEST)
        if self.scoring == "softmax":
            return softmax_top_k_routing(logits, self.k, self.normalize,
                                         self.scale)
        return sigmoid_top_k_routing(logits, bias, self.k, self.normalize,
                                     self.scale)


def _declare_step_metrics() -> None:
    """The three series a dropless layer sows, registered under their
    own help texts (``telemetry.publish_step_counters`` fills them)."""
    from analytics_zoo_tpu.common import telemetry
    reg = telemetry.get_registry()
    reg.counter("zoo_moe_assignments_total",
                "Token-to-expert assignments a dropless expert layer "
                "routed, per optimizer step: held=true those on the "
                "experts the layer holds", ("held", "layer"))
    reg.counter("zoo_moe_window_rows_total",
                "Rows of the held experts' first window, all computed, "
                "per optimizer step: used=false those that held no "
                "assignment (the price of a step time that does not "
                "follow the routing)", ("layer", "used"))
    reg.gauge("zoo_moe_load_imbalance",
              "Largest held expert's load over the mean load tokens * k "
              "/ n_experts, in the last optimizer step fetched",
              ("layer",))


def sow_last(module: nn.Module, name: str, value) -> None:
    """One number of this step into the ``counters`` collection (the name
    is the telemetry series: ``telemetry.publish_step_counters``)."""
    module.sow("counters", name, value, reduce_fn=lambda _, new: new,
               init_fn=lambda: 0)


class DroplessMoE(nn.Module):
    """Sparse feed-forward block that drops no token: a ``Router`` over
    ``n_experts`` (``scoring``: sigmoid scores with a selection-only
    ``expert_bias``, or a softmax and no bias), ``k`` experts a token,
    gated (SwiGLU) experts of width ``d_hidden``.

    ``held``: the ids of the experts THIS layer holds (default: all, the
    published layer). It routes over all ``n_experts``, computes
    ``sum_{j held} w_j E_j(x)`` with the weights normalised over all ``k``
    selected, and returns that partial sum; nothing stands in for the
    absent experts or their exchange. The shares of a partition of the
    experts add up to the whole layer.

    Sows, per step, into the ``counters`` collection (the Estimator
    fetches it with the losses where every step is a dispatch of its
    own; ``fit(steps_per_loop>1)`` and the cached epoch hand out the
    losses alone): ``zoo_moe_assignments_total{held=..}``,
    ``zoo_moe_window_rows_total{used=..}`` (the first window's rows, all
    computed; ``used=false`` those no assignment sat at) and
    ``zoo_moe_load_imbalance`` (the largest held expert's load over the
    mean load ``tokens * k / n_experts``)."""

    n_experts: int
    k: int
    d_hidden: int
    held: Optional[Tuple[int, ...]] = None
    normalize: bool = True
    scale: float = 1.0
    dtype: Optional[object] = None
    kernel_init: nn.initializers.Initializer = nn.initializers.lecun_normal()
    scoring: str = "sigmoid_bias"

    @nn.compact
    def __call__(self, x):
        held = tuple(range(self.n_experts)) if self.held is None \
            else tuple(self.held)
        tokens = x.reshape(-1, x.shape[-1])
        ids, weights = Router(self.n_experts, self.k, self.normalize,
                              self.scale, self.kernel_init, self.scoring,
                              name="router")(tokens)
        out, counts, window = HeldExperts(
            held, self.n_experts, self.d_hidden, self.dtype,
            self.kernel_init, name="experts")(tokens, ids, weights)
        _declare_step_metrics()
        n_all = ids.size
        n_held = jnp.sum(counts)
        sow_last(self, "zoo_moe_assignments_total{held=true}", n_held)
        sow_last(self, "zoo_moe_assignments_total{held=false}",
                 n_all - n_held)
        used = jnp.minimum(n_held, window)
        sow_last(self, "zoo_moe_window_rows_total{used=true}", used)
        sow_last(self, "zoo_moe_window_rows_total{used=false}",
                 window - used)
        sow_last(self, "zoo_moe_load_imbalance",
                 jnp.max(counts) * (self.n_experts / n_all))
        return out.reshape(x.shape).astype(x.dtype)
