"""The expert layer's row kernels (``ops/pallas/moe_rows.py``: rows
laid as whole tiles, and a token's rows summed by place) and the
hand-written backward round them (``ops/moe.py``), under the TPU
interpreter on the CPU, against the XLA formulation that runs wherever
the kernels do not: a gather, a scatter-add and autodiff's transposes.

The shapes leave block 0 on purpose (PERF.md 6, PR 27 (3): a kernel
tested only inside its first block proved nothing): several token tiles,
several row tiles, a stretch that starts past the first.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.ops import moe
from distributeddeeplearning_tpu.ops.pallas import moe_rows

T, K, E, D, F = 300, 3, 8, 256, 32  # three token tiles of 128


def gap(a, b):
    scale = max(float(jnp.abs(b).max()), 1e-6)
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()) / scale


def _operands(held, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (T, D))
    gates = jax.random.uniform(jax.random.fold_in(key, 1), (T, K), minval=0.1)
    w1, w3 = (
        0.2 * jax.random.normal(jax.random.fold_in(key, i), (held, D, F)) for i in (2, 3)
    )
    w2 = 0.2 * jax.random.normal(jax.random.fold_in(key, 4), (held, F, D))
    return x, gates, w1, w3, w2


def _random_experts(seed=5):
    """Every token's K experts, distinct, as a top-k would give them."""
    scores = jax.random.normal(jax.random.PRNGKey(seed), (T, E))
    return jax.lax.top_k(scores, K)[1].astype(jnp.int32)


def _routing(case):
    """``(experts [T, K], first, held, cap, start)`` of a case."""
    experts = _random_experts()
    if case == "several-tiles":  # two row tiles of 256, the second part full
        return experts, 2, 3, 384, 0
    if case == "two-and-none":
        experts = experts.at[0].set(jnp.asarray([2, 3, 7]))  # two held
        experts = experts.at[1].set(jnp.asarray([0, 1, 7]))  # none
        return experts, 2, 3, 384, 0
    if case == "an-expert-drew-nothing":
        experts = jnp.where(experts == 3, 7 - jnp.arange(K)[None, :] % 2, experts)
        return experts, 2, 3, 384, 0
    if case == "start-past-the-first":  # 4 of 8 held draw ~450 pairs
        return experts, 2, 4, 256, 256
    if case == "all-held":
        return experts, 0, E, T * K, 0
    if case == "none-held":
        return jnp.minimum(experts, 5), 6, 2, 384, 0
    raise AssertionError(case)


def _stretch(with_places, experts, first, held, cap, start, x, gates, w1, w3, w2):
    routed = moe.Routed(experts, gates)
    key, drawn = moe._held_keys(experts, first, held)
    order = jnp.argsort(key, stable=True)
    order = jnp.pad(order, (0, -order.shape[0] % cap))
    place = moe._places(key, drawn) if with_places else None
    return moe._held_part(
        x, routed, order, place, drawn, w1, w3, w2, start, cap=cap, activation="silu"
    )


@pytest.mark.parametrize("case", [
    "several-tiles", "two-and-none", "an-expert-drew-nothing",
    "start-past-the-first", "all-held", "none-held",
])
def test_the_kernels_move_what_xla_moves(case):
    """One stretch under the hand-written backward and by XLA's scatter-
    adds: the output (``rows_by_place`` sums it under the gates), ``dx``
    (``rows_by_place`` again, gate one), the weights' gradients (which
    read ``d out``: ``dy`` gathered by token) and the gates' cotangent (a
    row dot read back by place)."""
    experts, first, held, cap, start = _routing(case)
    operands = _operands(held)
    total = int(moe._held_keys(experts, first, held)[1].sum())
    if case == "start-past-the-first":
        assert total > start  # the stretch holds pairs
    if case == "none-held":
        assert total == 0
    if case == "an-expert-drew-nothing":
        assert int(moe._held_keys(experts, first, held)[1][1]) == 0

    def run(with_places):
        def f(*operands):
            y = _stretch(with_places, experts, first, held, cap, start, *operands)
            return jnp.sum(jnp.sin(y)), y
        (_, y), grads = jax.jit(
            jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True)
        )(*operands)
        return (y,) + grads

    for name, got, want in zip(
        ("y", "dx", "d gates", "d w1", "d w3", "d w2"), run(True), run(False)
    ):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        assert gap(got, want) < 2e-5, (case, name)
        if total == 0:
            assert float(jnp.abs(got).max()) == 0.0, name


def test_rows_past_the_pairs_are_never_fetched():
    """``rows_by_place`` reads only the rows that places name: rows that
    hold NaN where no place points do no harm, a place past the rows adds
    nought, and a token with no place at all comes out as nought."""
    rows = jax.random.normal(jax.random.PRNGKey(0), (72, D)).astype(jnp.bfloat16)
    rows = rows.at[50:].set(jnp.nan)
    place = jax.random.randint(jax.random.PRNGKey(1), (T, K), 0, 400)
    place = jnp.where(place >= 50, 72 + place, place).at[7].set(10**6)
    gate = jax.random.uniform(jax.random.PRNGKey(2), (T, K))
    y = moe_rows.rows_by_place(moe_rows.to_tiles(rows), place, gate, width=D, tile=64)
    ext = jnp.concatenate([rows[:50].astype(jnp.float32), jnp.zeros((1, D))])
    want = (ext[jnp.minimum(place, 50)] * gate[..., None]).sum(1)
    assert gap(y, want) < 1e-6
    assert float(jnp.abs(y[7]).max()) == 0.0


@pytest.mark.parametrize("width,tiles", [(2048, 16), (2560, 24), (128, 8)])
def test_a_row_lies_as_whole_tiles(width, tiles):
    """``to_tiles``: a row a leading index and whole tiles of 8 × 128,
    over several grid steps; the lanes past the width are nobody's."""
    a = jnp.arange(40 * width, dtype=jnp.float32).reshape(40, width)
    tiled = moe_rows.to_tiles(a, tile=16)
    assert tiled.shape == (40, tiles, 128)
    assert np.array_equal(
        np.asarray(tiled.reshape(40, -1)[:, :width]), np.asarray(a)
    )
    assert moe_rows.supports(width) and not moe_rows.supports(width + 32)


@pytest.mark.parametrize("skew", [0.0, 50.0], ids=["even", "onto-one-expert"])
def test_the_layer_whole_by_the_kernels_and_by_xla(monkeypatch, skew):
    """``held_experts_ffn`` with the rule answered as on the chip: value
    and gradient of ``x``, the three weights and the router's logits are
    XLA's, in the usual stretch and (the skewed router) through the
    ``cond`` and the ``scan`` over further stretches."""
    # the interpreter's callbacks are effects, which `jax.checkpoint`
    # does not take: the layer is the same function without it
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: f)
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    first, held = 2, 2
    x, _, w1, w3, w2 = _operands(held, seed=1)
    x = x.at[:, 0].set(1.0)
    router = jax.random.normal(jax.random.PRNGKey(9), (D, E)).at[0, 2:4].add(skew)
    logits = jnp.matmul(x, router, precision="highest")

    def layer(x, logits, w1, w3, w2):
        y, drawn = moe.held_experts_ffn(
            x, moe.route_top_k(logits, K), w1, w3, w2, first=first, num_experts=E
        )
        return jnp.sum(jnp.sin(y)), (y, drawn)

    def run(impl):
        monkeypatch.setattr(moe, "rows_impl", lambda x: impl)
        jax.clear_caches()
        (_, (y, drawn)), grads = jax.jit(
            jax.value_and_grad(layer, argnums=(0, 1, 2, 3, 4), has_aux=True)
        )(x, logits, w1, w3, w2)
        return (y,) + grads, drawn

    got, drawn = run("kernel")
    want, _ = run("xla")
    cap = moe.usual_cap(T * K, held, E)
    assert (int(drawn.sum()) > cap) == bool(skew)  # further stretches ran
    for name, a, b in zip(("y", "dx", "d logits", "d w1", "d w3", "d w2"), got, want):
        assert gap(a, b) < 2e-5, name
