"""``ops/ssm.py``: the chunked scan against the token-by-token
recurrence it stands for (forward and every gradient, float32, over a
row of one chunk, of several, and with a ragged last one), the state
carried across chunks against one chunk that holds the whole row, the
causal depthwise convolution against a loop over taps and positions,
and causality: a row's output up to a position hangs on nothing after
it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.ops import ssm

H, P, N = 4, 8, 16
NAMES = ("xs", "dt", "a", "b", "c", "d")


def operands(length, groups=1, batch=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (
        jax.random.normal(ks[0], (batch, length, H, P)),
        jax.nn.softplus(jax.random.normal(ks[1], (batch, length, H)) - 1.0),
        -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.0)),
        jax.random.normal(ks[3], (batch, length, groups, N)),
        jax.random.normal(ks[4], (batch, length, groups, N)),
        jax.random.normal(ks[5], (H,)),
    )


def recurrence(xs, dt, a, b, c, d):
    """``S_t = exp(Δ a) S_{t−1} + Δ xs ⊗ B``, ``y = S·C + D xs``, a
    position at a time."""
    rep = xs.shape[2] // b.shape[2]
    b, c = jnp.repeat(b, rep, axis=2), jnp.repeat(c, rep, axis=2)

    def step(state, at):
        x, delta, b_t, c_t = at  # [B,H,P], [B,H], [B,H,N], [B,H,N]
        state = (
            jnp.exp(delta * a)[..., None, None] * state
            + (delta[..., None] * x)[..., None] * b_t[:, :, None, :]
        )
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t) + d[:, None] * x

    first = jnp.zeros((xs.shape[0], xs.shape[2], xs.shape[3], b.shape[3]))
    _, y = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(v, 1, 0) for v in (xs, dt, b, c)
    ))
    return jnp.moveaxis(y, 0, 1)


def gap(got, want):
    return float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-30))


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("length", [8, 32, 27, 5])
def test_the_chunked_scan_is_the_recurrence_forward_and_backward(length, groups):
    """Chunk 8: one chunk, four, three and a ragged fourth, a row
    shorter than a chunk. 1e-5 of the largest entry, float32."""
    args = operands(length, groups)
    weigh = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    chunked = lambda *v: ssm.ssd_scan(*v, chunk=8)  # noqa: E731
    got, want = jax.jit(chunked)(*args), jax.jit(recurrence)(*args)
    assert got.shape == args[0].shape and gap(got, want) < 1e-5
    loss = lambda f: (lambda *v: jnp.sum(f(*v) * weigh))  # noqa: E731
    every = tuple(range(6))
    g_got = jax.jit(jax.grad(loss(chunked), every))(*args)
    g_want = jax.jit(jax.grad(loss(recurrence), every))(*args)
    for name, x, y in zip(NAMES, g_got, g_want):
        assert gap(x, y) < 1e-5, name


def test_the_state_carried_across_chunks_is_one_chunk_s():
    args = operands(32)
    whole = ssm.ssd_scan(*args, chunk=32)
    for chunk in (4, 8, 16):
        assert gap(ssm.ssd_scan(*args, chunk=chunk), whole) < 1e-5, chunk


def test_a_row_s_output_hangs_on_nothing_after_it():
    xs, dt, a, b, c, d = operands(27, seed=3)
    other = operands(27, seed=4)
    cut = 13
    splice = lambda x, y: jnp.concatenate([x[:, :cut], y[:, cut:]], axis=1)  # noqa: E731
    changed = ssm.ssd_scan(
        splice(xs, other[0]), splice(dt, other[1]), a,
        splice(b, other[3]), splice(c, other[4]), d, chunk=8,
    )
    same = ssm.ssd_scan(xs, dt, a, b, c, d, chunk=8)
    assert bool(jnp.all(changed[:, :cut] == same[:, :cut]))
    assert gap(changed[:, cut:], same[:, cut:]) > 1e-2
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 27, 6))
    w = jax.random.normal(jax.random.PRNGKey(6), (6, 4))
    bias = jnp.zeros((6,))
    y = ssm.causal_conv1d(x, w, bias)
    later = ssm.causal_conv1d(x.at[:, cut:].set(7.0), w, bias)
    assert bool(jnp.all(later[:, :cut] == y[:, :cut]))


def test_a_step_of_nought_neither_decays_nor_writes():
    """What pads a ragged row: the state passes a ``Δ = 0`` position
    unchanged, so the positions after a stretch of them read what they
    would have read without it."""
    xs, dt, a, b, c, d = operands(16, seed=7)
    hole = dt.at[:, 4:12].set(0.0)
    with_hole = ssm.ssd_scan(xs, hole, a, b, c, d, chunk=8)
    keep = np.r_[0:4, 12:16]
    without = ssm.ssd_scan(
        xs[:, keep], dt[:, keep], a, b[:, keep], c[:, keep], d, chunk=8
    )
    assert gap(with_hole[:, keep], without) < 1e-5


@pytest.mark.parametrize("length,taps", [(9, 4), (3, 4), (12, 2)])
def test_the_convolution_against_a_loop(length, taps):
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (2, length, 5)))
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (5, taps)))
    bias = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (5,)))
    want = np.zeros_like(x)
    for t in range(length):
        for ch in range(5):
            want[:, t, ch] = bias[ch] + sum(
                w[ch, j] * x[:, t - (taps - 1) + j, ch]
                for j in range(taps) if t - (taps - 1) + j >= 0
            )
    got = ssm.causal_conv1d(jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias))
    assert gap(got, jnp.asarray(want)) < 1e-6
    g = jax.grad(lambda *v: jnp.sum(ssm.causal_conv1d(*v) ** 2), (0, 1, 2))(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias)
    )
    assert gap(g[2], 2 * jnp.sum(jnp.asarray(want), (0, 1))) < 1e-5
    assert g[0].shape == x.shape and g[1].shape == w.shape


def test_the_products_run_in_the_operands_dtype_and_the_state_in_float32():
    args = operands(24)
    half = tuple(
        v.astype(jnp.bfloat16) if name in ("xs", "b", "c") else v
        for name, v in zip(NAMES, args)
    )
    y = ssm.ssd_scan(*half, chunk=8)
    assert y.dtype == jnp.bfloat16
    assert gap(y.astype(jnp.float32), recurrence(*args)) < 3e-2
    jaxpr = str(jax.make_jaxpr(lambda *v: ssm.ssd_scan(*v, chunk=8))(*half))
    assert "f32[2,4,8,16]" in jaxpr  # the carried state


def test_the_scan_counts_what_it_chose():
    obs.reset()
    ssm.ssd_scan(*operands(27), chunk=8)
    seen = [e for e in obs.get_bus().ring if e.get("name") == "ssm.impl.xla"]
    assert len(seen) == 1
    labels = seen[0]["labels"] if "labels" in seen[0] else seen[0]
    assert labels["chunks"] == 4 and labels["padded"] == 5 and labels["chunk"] == 8
    assert labels["heads"] == H and labels["state"] == N and labels["head_dim"] == P
    obs.reset()


def test_heads_that_do_not_divide_into_groups_are_refused():
    xs, dt, a, b, c, d = operands(8)
    with pytest.raises(ValueError, match="groups"):
        ssm.ssd_scan(xs, dt, a, jnp.tile(b, (1, 1, 3, 1)), jnp.tile(c, (1, 1, 3, 1)), d, chunk=8)
