"""Expert routing without dropped tokens, over a share of the experts.

The layer that expert parallelism asks for (``models/moe.MoEMlpBlock``
is the other one: a capacity per expert, one-hot dispatch, overflow
dropped). A token chooses ``k`` of ``E`` experts (:func:`route_top_k`,
float32); this process holds ``held`` of them, ``first .. first + held −
1``, and :func:`held_experts_ffn` computes the part of the layer's
output that those give. No capacity and no drop:

* ``moe_dispatch`` — the ``T·k`` (token, expert) pairs are sorted by
  held expert, the pairs of experts not held last, and taken a stretch
  of ``cap`` sorted pairs at a time: their tokens are gathered. Every
  shape is static. ``cap`` is the expected share of the pairs with room
  to spare (:data:`CAP_SLACK`), and **not a capacity**: the first
  stretch is computed always, and each further one (``T·k / cap`` in
  all, so that every pair has a place) where the held experts drew
  pairs that reach into it, under a ``lax.cond``. Rows past the pairs
  that are there are masked out, forward and backward.
* ``moe_experts`` — three grouped matrix products over a stretch's
  ``cap`` rows (the held experts' rows, and nought for the rest: a
  layer's load on the held experts swings with what its tokens have in
  common, between nothing and several times its share, and a step that
  took as long as its routing asked could not be timed to a percent):
  ``W1``, ``W3`` up, gated by SiLU or, in a ReGLU layer, by ReLU
  (:data:`ACTIVATIONS`), ``W2`` down: ``jax.lax.ragged_dot``.
* ``moe_combine`` — each pair's row times its gate, added into its
  token's row (float32).

On one chip there is no exchange. What the absent experts would have
added is absent from the result, as in the plain reference.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# Room over the expected share of the pairs that the first stretch holds
# rows for. A layer's load follows what the tokens have in common (under
# block diffusion every masked position is one token and routes alike), so
# it is wide; a step past it computes further stretches.
CAP_SLACK = 2.0
_ROW_TILE = 512  # the usual branch holds whole tiles of this many rows

ROUTE, DISPATCH, EXPERTS, COMBINE = (
    "moe_route", "moe_dispatch", "moe_experts", "moe_combine"
)
# the gate of an expert's up-projection, by the name a spec gives it
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


class Routed(NamedTuple):
    """A token's choices: expert ids ``[T, k]`` and their gates, float32."""

    experts: jnp.ndarray
    gates: jnp.ndarray


def route_top_k(logits: jnp.ndarray, k: int, renormalise: bool = True) -> Routed:
    """Softmax over all the experts in float32, the ``k`` largest, their
    probabilities renormalised to sum to one (``norm_topk_prob``)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, experts = lax.top_k(probs, k)
    if renormalise:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return Routed(experts.astype(jnp.int32), gates)


def _grouped_matmul(lhs, rhs, sizes):
    """``lhs[rows of group g] @ rhs[g]`` for the groups in row order
    (``jax.lax.ragged_dot``: XLA's own grouped product, a Mosaic kernel
    on the TPU); rows past the last group come out as nought on the CPU
    and unwritten on the TPU, so the caller masks them.
    ``jax.experimental.pallas.ops.tpu.megablox.gmm`` computes the same,
    but states no varying axes for its outputs and so cannot stand
    inside the engines' ``shard_map`` (``check_vma``)."""
    return lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=lhs.dtype)


def _held_keys(experts, first: int, held: int):
    """A pair's sort key ``[T·k]``: its expert's place among the held
    ones, ``held`` for an expert that is not; and the pairs each held
    expert drew, ``[held]``."""
    local = experts.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    drawn = jnp.sum(
        key[:, None] == jnp.arange(held, dtype=key.dtype)[None, :],
        axis=0, dtype=jnp.int32,
    )
    return key, drawn


def _held_part(x, routed: Routed, order, drawn, w1, w3, w2, start, *,
               cap: int, activation: str):
    """The held experts' part of the layer from the ``cap`` sorted pairs
    ``order[start : start + cap]``."""
    t, k = routed.experts.shape
    with jax.named_scope(DISPATCH):
        pairs = lax.dynamic_slice_in_dim(order, start, cap)
        token = pairs // k
        # the rows of each held expert that fall in this stretch
        ends = jnp.cumsum(drawn)
        sizes = jnp.clip(
            jnp.minimum(ends, start + cap) - jnp.maximum(ends - drawn, start),
            0, None,
        ).astype(jnp.int32)
        there = start + jnp.arange(cap) < ends[-1]
        # every stretch computes all its rows: those past the pairs that
        # are there are nought and ride in the last held expert's group,
        # so that a step's time does not follow the routing
        sizes = sizes.at[-1].add(cap - jnp.sum(sizes))
        gate = routed.gates.reshape(-1)[pairs]
        # nought past the pairs that are there, and so is their cotangent:
        # a grouped product leaves the rows past its last group unwritten,
        # backward too, and those rows name real tokens
        rows = jnp.where(there[:, None], x[token], 0)
    with jax.named_scope(EXPERTS):
        up = _grouped_matmul(rows, w1, sizes).astype(jnp.float32)
        up = ACTIVATIONS[activation](up) * _grouped_matmul(rows, w3, sizes)
        up = jnp.where(there[:, None], up, 0.0)  # as for `rows`
        out = _grouped_matmul(up.astype(x.dtype), w2, sizes)
    with jax.named_scope(COMBINE):
        out = jnp.where(there[:, None], out.astype(jnp.float32), 0.0)
        return jnp.zeros((t, x.shape[1]), jnp.float32).at[token].add(
            out * gate[:, None]
        )


def usual_cap(pairs: int, held: int, experts: int) -> int:
    """Rows of one stretch: the expected share of ``pairs`` times
    :data:`CAP_SLACK`, in whole row tiles, at most all the pairs."""
    want = math.ceil(pairs * held / experts * CAP_SLACK)
    return min(pairs, -(-want // _ROW_TILE) * _ROW_TILE)


def held_experts_ffn(
    x: jnp.ndarray,
    routed: Routed,
    w1: jnp.ndarray,
    w3: jnp.ndarray,
    w2: jnp.ndarray,
    *,
    first: int,
    num_experts: int,
    activation: str = "silu",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``y[t] = Σ_{e held, e chosen by t} gate[t, e] · W2_e(act(W1_e x_t) ⊙
    W3_e x_t)`` for ``x [T, D]`` and weights ``[held, D, F]``, ``[held, D,
    F]``, ``[held, F, D]`` (cast to ``x``'s dtype here); ``activation``
    names ``act`` (:data:`ACTIVATIONS`). Returns ``y [T, D]`` and the
    pairs each held expert drew, ``[held]`` int32.

    The whole of it is recomputed in the backward pass (``jax.
    checkpoint``): kept are ``x`` and the routing, not the gathered
    rows, the products or the cast weights, 0.4 GiB a layer at 8,192
    positions (the three products forward are a fortieth of a step)."""
    held = w1.shape[0]
    with jax.named_scope(DISPATCH):
        key, drawn = _held_keys(routed.experts, first, held)
        order = jnp.argsort(key, stable=True)
    cap = usual_cap(routed.experts.size, held, num_experts)
    y = jax.checkpoint(
        functools.partial(_stretches, cap=cap, activation=activation)
    )(x, routed, order, drawn, w1, w3, w2)
    return y, drawn


def _stretches(x, routed: Routed, order, drawn, w1, w3, w2, *, cap: int,
               activation: str):
    pairs = routed.experts.size
    stretches = -(-pairs // cap)
    if stretches > 1:  # the last stretch may reach past the pairs
        order = jnp.pad(order, (0, stretches * cap - pairs))
    operands = (x, routed, order, drawn) + tuple(
        w.astype(x.dtype) for w in (w1, w3, w2)
    )
    held_part = functools.partial(_held_part, cap=cap, activation=activation)
    y = held_part(*operands, 0)
    if stretches > 1:
        # Past the usual stretch: only a step whose held experts drew
        # more than `cap` pairs goes in here at all (one `cond`, whose
        # other branch hands `y` on as it is). The scan's body is a
        # checkpoint of its own, so that it keeps the stretch's start and
        # not a copy of the operands a stretch.
        @jax.checkpoint
        def further(start):
            return lax.cond(
                start < jnp.sum(drawn),
                lambda: held_part(*operands, start),
                lambda: jnp.zeros_like(y),
            )

        def overflow(y):
            return lax.scan(
                lambda y, start: (y + further(start), None),
                y, cap * jnp.arange(1, stretches),
            )[0]

        y = lax.cond(jnp.sum(drawn) > cap, overflow, lambda y: y, y)
    return y.astype(x.dtype)
