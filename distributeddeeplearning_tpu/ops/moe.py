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

**How the rows are moved** is a rule (:func:`rows_impl`), not a knob, and
both ways share the router, the keys, ``cap`` and the products:

* ``"kernel"`` (a TPU, a width of whole lanes): the permutation is made
  once a forward, ``order`` by the one sort of the pairs' keys and each
  pair's place by counting (:func:`_places`), and applied under
  hand-written backward rules (:func:`_take_rows`, :func:`_sum_rows`):
  the transpose of a gather by a permutation is a gather by its
  inverse. Rows read by index are XLA's gather (dispatch reads ``x`` by
  token, combine's backward ``dy`` by token); rows summed by place are
  the kernel of ``ops/pallas/moe_rows.py``, a token's ≤ k rows in
  float32, of which only those that are there are fetched (combine sums
  ``out`` under the gates, dispatch's backward ``d rows`` under a gate
  of one). The gates' cotangent is a row dot read back by place.
  Nothing is scattered and no row index sorted.
* ``"xla"`` (everywhere else: the CPU tests, the tiny specs): a gather,
  a scatter-add into ``[T, D]`` float32, and autodiff's transposes of
  both.

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

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.ops.pallas import moe_rows

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


def _places(key, drawn):
    """Each pair's place in the order ``jnp.argsort(key, stable=True)``
    gives, ``[T·k]``, by counting and with no sort: its group's start (a
    cumulative sum of ``drawn``) plus its rank in the group (a cumulative
    sum of the ``[T·k, held]`` one-hot along the pairs: tokens ascend
    inside an expert's group). ``T·k``, a place no stretch holds, for a
    pair whose expert is not held."""
    held = drawn.shape[0]
    mine = key[:, None] == jnp.arange(held, dtype=key.dtype)[None, :]
    before = jnp.cumsum(mine, axis=0, dtype=jnp.int32) - 1
    starts = jnp.cumsum(drawn) - drawn
    place = jnp.sum(jnp.where(mine, before + starts[None, :], 0), axis=1)
    return jnp.where(key < held, place, key.shape[0]).astype(jnp.int32)


def rows_impl(x) -> str:
    """How a layer's rows are moved, from what the call can see:
    ``"kernel"`` (``ops/pallas/moe_rows.py`` under the hand-written
    backward) on a TPU where ``x [T, D]`` is local (one device, or inside
    ``shard_map``: a custom call under multi-device GSPMD would force
    replication) and ``D`` is whole lanes; ``"xla"``, gather and
    scatter-add and their transposes, everywhere else (the CPU tests, the
    tiny specs)."""
    local = bool(getattr(jax.typeof(x), "vma", ())) or jax.device_count() == 1
    if jax.default_backend() == "tpu" and local and moe_rows.supports(x.shape[1]):
        return "kernel"
    return "xla"


@jax.custom_vjp
def _take_rows(x, token, local):
    """``x[token]``: XLA's gather, which moves a row as fast as a kernel of
    ours that copies it (PERF.md 6, PR 32), with a backward of its own.
    ``local [T, k]`` is each pair's row in this stretch (the stretch's
    length for a pair that has none): the backward sums ``d rows`` by it,
    a token's ≤ k rows in float32, since the transpose of a gather by a
    permutation is a gather by its inverse and never a scatter-add. Rows
    past the pairs that are there hold some token's row and not nought:
    no place names them, so their cotangent is never read, and the mask
    on the experts' ``up`` keeps them out of the weights' gradients."""
    del local
    return x[token]


def _take_rows_fwd(x, token, local):
    return x[token], local


def _take_rows_bwd(local, d_rows):
    cap = d_rows.shape[0]
    dx = moe_rows.rows_by_place(
        moe_rows.to_tiles(d_rows), local, (local < cap).astype(jnp.float32),
        width=d_rows.shape[1], out_dtype=d_rows.dtype,
    )
    return dx, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def _sum_rows(out, gates, pairs, n, local):
    """``y[t] = Σ_j gates[t, j] · out[local[t, j]]`` over a token's pairs
    that have a row in this stretch, float32 (``rows_by_place``: rows past
    the pairs that are there are never read). ``pairs [cap]`` and ``n`` are
    the stretch's pairs in row order and how many are there: the backward
    reads ``dy`` by them."""
    del pairs, n
    cap = out.shape[0]
    return moe_rows.rows_by_place(
        moe_rows.to_tiles(out), local, jnp.where(local < cap, gates, 0.0),
        width=out.shape[1],
    )


def _sum_rows_fwd(out, gates, pairs, n, local):
    return _sum_rows(out, gates, pairs, n, local), (out, gates, pairs, n, local)


def _sum_rows_bwd(res, dy):
    out, gates, pairs, n, local = res
    cap = out.shape[0]
    k = gates.shape[1]
    # dy is y's cotangent through a cast to out's dtype: nothing is lost
    there = jnp.arange(cap) < n
    dy_rows = jnp.where(
        there[:, None], dy.astype(out.dtype)[pairs // k].astype(jnp.float32), 0.0
    )
    d_out = (dy_rows * gates.reshape(-1)[pairs][:, None]).astype(out.dtype)
    d_gate = jnp.sum(
        jnp.where(there[:, None], out.astype(jnp.float32), 0.0) * dy_rows, axis=1
    )
    d_gates = jnp.where(local < cap, d_gate[jnp.minimum(local, cap - 1)], 0.0)
    return d_out, d_gates.astype(gates.dtype), None, None, None


_sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)


def _held_part(x, routed: Routed, order, place, drawn, w1, w3, w2, start, *,
               cap: int, activation: str):
    """The held experts' part of the layer from the ``cap`` sorted pairs
    ``order[start : start + cap]``; ``place`` is :func:`_places`' where
    the kernels move the rows, and None where XLA does."""
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
        n = jnp.sum(sizes)
        there = jnp.arange(cap) < n
        # every stretch computes all its rows: those past the pairs that
        # are there are nought and ride in the last held expert's group,
        # so that a step's time does not follow the routing
        sizes = sizes.at[-1].add(cap - n)
        if place is None:
            # nought past the pairs that are there, and so is their
            # cotangent: a grouped product leaves the rows past its last
            # group unwritten, backward too, and those rows name real
            # tokens, whom the gather's transpose would hand it
            rows = jnp.where(there[:, None], x[token], 0)
        else:
            # no place names a row past the pairs that are there, so
            # nothing reads it back: it may hold whatever token it names
            local = jnp.where(
                (place >= start) & (place < start + cap), place - start, cap
            ).reshape(t, k)
            rows = _take_rows(x, token, local)
    with jax.named_scope(EXPERTS):
        up = _grouped_matmul(rows, w1, sizes).astype(jnp.float32)
        up = ACTIVATIONS[activation](up) * _grouped_matmul(rows, w3, sizes)
        up = jnp.where(there[:, None], up, 0.0)  # nought past the pairs, both ways
        out = _grouped_matmul(up.astype(x.dtype), w2, sizes)
    with jax.named_scope(COMBINE):
        if place is not None:  # reads only the rows that are there
            return _sum_rows(out, routed.gates, pairs, n, local)
        gate = routed.gates.reshape(-1)[pairs]
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

    The rows are moved by the kernels or by XLA (:func:`rows_impl`,
    counted ``moe.rows.impl.<kernel|xla>`` once a traced layer). Sorted,
    once a forward and outside the checkpoint, are the ``T·k`` pairs'
    keys (``order``, which names a stretch's tokens in row order); the
    kernels' path also counts each pair's place (:func:`_places`), and
    sorts nothing else: no row index, forward or backward.

    The whole of it is recomputed in the backward pass (``jax.
    checkpoint``): kept are ``x``, the routing, ``order`` and the
    places, not the gathered rows, the products or the cast weights, 0.4
    GiB a layer at 8,192 positions (the three products forward are a
    fortieth of a step)."""
    held = w1.shape[0]
    cap = usual_cap(routed.experts.size, held, num_experts)
    impl = rows_impl(x)
    obs.counter(
        f"moe.rows.impl.{impl}", rows=cap, width=x.shape[1], tokens=x.shape[0],
        k=routed.experts.shape[1],
    )
    with jax.named_scope(DISPATCH):
        key, drawn = _held_keys(routed.experts, first, held)
        order = jnp.argsort(key, stable=True)
        place = _places(key, drawn) if impl == "kernel" else None
    y = jax.checkpoint(
        functools.partial(_stretches, cap=cap, activation=activation)
    )(x, routed, order, place, drawn, w1, w3, w2)
    return y, drawn


def rows_live_share(drawn, pairs: int, num_experts: int):
    """Of the rows of the stretches a layer computes (``drawn [held]`` of
    its ``pairs`` fell to held experts), the share that are pairs: what
    the row kernel moves of what the products compute."""
    cap = usual_cap(pairs, drawn.shape[0], num_experts)
    there = jnp.sum(drawn)
    return there / (cap * jnp.maximum(1, -(-there // cap)))


def _stretches(x, routed: Routed, order, place, drawn, w1, w3, w2, *, cap: int,
               activation: str):
    pairs = routed.experts.size
    stretches = -(-pairs // cap)
    if stretches > 1:  # the last stretch may reach past the pairs
        order = jnp.pad(order, (0, stretches * cap - pairs))
    operands = (x, routed, order, place, drawn) + tuple(
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
