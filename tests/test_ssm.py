"""``ops/ssm.py``: the chunked scan against the token-by-token
recurrence it stands for (forward and every gradient, float32, over a
row of one chunk, of several, and with a ragged last one), the state
carried across chunks against one chunk that holds the whole row, the
causal depthwise convolution against a loop over taps and positions,
and causality: a row's output up to a position hangs on nothing after
it. The same cases run ``ops/pallas/ssd.py``'s kernels in interpret mode
against the XLA form (sizes that tile: two head blocks of 8 heads of 64,
a state of 128, chunks of 128 and 256), and the rule that chooses
between the two is pinned. The kernels' tests are jitted and each has a
time limit of its own (PERF.md 6, PR 32 (2): interpret mode's callbacks
un-jitted hung a test for its whole timeout)."""

import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.ops import ssm

H, P, N = 4, 8, 16
# what the kernels tile: two head blocks of 8 (one a group of two)
KERNEL_SIZES = (16, 64, 128)
NAMES = ("xs", "dt", "a", "b", "c", "d")


def operands(length, groups=1, batch=2, seed=0, sizes=(H, P, N)):
    h, p, n = sizes
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (
        jax.random.normal(ks[0], (batch, length, h, p)),
        jax.nn.softplus(jax.random.normal(ks[1], (batch, length, h)) - 1.0),
        -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.0)),
        jax.random.normal(ks[3], (batch, length, groups, n)),
        jax.random.normal(ks[4], (batch, length, groups, n)),
        jax.random.normal(ks[5], (h,)),
    )


@pytest.fixture
def on_kernels(monkeypatch):
    """Gives the test 300 s, and a switch: once called, the rule answers
    as on the chip and ``ssd_scan`` runs the kernels (in interpret mode);
    before, it runs the XLA form they are held against. What is jitted
    before the switch stays what it was: jit afresh after it."""
    def expired(signum, frame):
        raise TimeoutError("a kernel test ran over its 300 s")

    before = signal.signal(signal.SIGALRM, expired)
    signal.alarm(300)
    yield lambda: monkeypatch.setattr(ssm, "resolve_impl", lambda *a, **k: "pallas")
    signal.alarm(0)
    signal.signal(signal.SIGALRM, before)


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


def weighed(f, args):
    """``f(*args)`` and the gradients of a weighted sum of it in all six
    operands: one jitted program, the output its aux."""
    weigh = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def loss(*v):
        y = f(*v)
        return jnp.sum(y.astype(jnp.float32) * weigh), y

    (_, y), grads = jax.jit(jax.value_and_grad(loss, tuple(range(6)), has_aux=True))(*args)
    return y, grads


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("length", [8, 32, 27, 5])
def test_the_chunked_scan_is_the_recurrence_forward_and_backward(length, groups):
    """Chunk 8: one chunk, four, three and a ragged fourth, a row
    shorter than a chunk. 1e-5 of the largest entry, float32."""
    args = operands(length, groups)
    got, g_got = weighed(lambda *v: ssm.ssd_scan(*v, chunk=8), args)
    want, g_want = weighed(recurrence, args)
    assert got.shape == args[0].shape and gap(got, want) < 1e-5
    for name, x, y in zip(NAMES, g_got, g_want):
        assert gap(x, y) < 1e-5, name


def both_forms(on_kernels, args, chunk):
    """``(y, gradients)`` of a weighted sum of ``ssd_scan`` in all six
    operands, by the XLA form and then by the kernels, jitted."""
    out = []
    for switch in (lambda: None, on_kernels):
        switch()
        out.append(weighed(lambda *v: ssm.ssd_scan(*v, chunk=chunk), args))
    return out


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("length,chunk", [(384, 128), (300, 128), (512, 256), (128, 256)])
def test_the_kernels_are_the_xla_form_forward_and_backward(on_kernels, length, chunk, groups):
    """Two head blocks of 8 (the two groups' own where there are two):
    three chunks of one tile of ``L``; a ragged third padded with ``Δ =
    0``; two chunks of three tiles each; a row shorter than the chunk.
    Float32: the same sums in another order, ``Δ_s`` inside ``L``'s
    exponent and ``dc_t`` from ``Σ_p dy ⊙ y``: 5e-5 of the largest entry,
    2e-4 for ``a``, whose gradient sums every position of every row."""
    args = operands(length, groups, sizes=KERNEL_SIZES)
    (want, g_want), (got, g_got) = both_forms(on_kernels, args, chunk)
    assert got.shape == args[0].shape and gap(got, want) < 5e-5
    for name, x, y in zip(NAMES, g_got, g_want):
        assert x.shape == y.shape and gap(x, y) < (2e-4 if name == "a" else 5e-5), name


def test_the_kernels_take_bfloat16_operands_and_carry_the_state_in_float32(on_kernels):
    """Products on bfloat16 operands, ``Δ``, ``c_t``, ``L`` and the state
    float32: ``y`` to the XLA form's last bit but a rounding; the
    gradients to what rounding a cotangent to bfloat16 before its product
    moves (the XLA form's transposes multiply them in float32 on the
    CPU), and to the float32 recurrence as the XLA form is."""
    full = operands(384, sizes=KERNEL_SIZES)
    half = tuple(
        v.astype(jnp.bfloat16) if name in ("xs", "b", "c") else v
        for name, v in zip(NAMES, full)
    )
    (want, g_want), (got, g_got) = both_forms(on_kernels, half, 128)
    assert got.dtype == jnp.bfloat16
    assert gap(got.astype(jnp.float32), want.astype(jnp.float32)) < 1e-2
    assert gap(got.astype(jnp.float32), jax.jit(recurrence)(*full)) < 3e-2
    for name, x, y in zip(NAMES, g_got, g_want):
        assert x.dtype == y.dtype, name
        assert gap(x.astype(jnp.float32), y.astype(jnp.float32)) < 2e-2, name
    from distributeddeeplearning_tpu.ops.pallas import ssd

    xs, dt, a, b, c, _ = half
    cum = jnp.cumsum((dt * a).reshape(2, 3, 128, 16), axis=2).reshape(2, 384, 16)
    _, states = ssd._forward(xs, dt, cum, b, c, 128, True)
    # the state as it entered each chunk: [B, chunks, H/hb, N, hb·P]
    assert states.shape == (2, 3, 2, 128, 512) and states.dtype == jnp.float32
    assert not bool(jnp.any(states[:, 0])) and bool(jnp.any(states[:, 1]))


def test_the_state_carried_across_chunks_is_one_chunk_s():
    args = operands(32)
    whole = ssm.ssd_scan(*args, chunk=32)
    for chunk in (4, 8, 16):
        assert gap(ssm.ssd_scan(*args, chunk=chunk), whole) < 1e-5, chunk


@pytest.mark.parametrize("length", [256, 512])
def test_the_kernels_state_carried_across_chunks_is_one_chunk_s(on_kernels, length):
    """Chunks of 128 against chunks of 256 (the whole row, or its two
    halves); 5e-5: rows many times the XLA form's above, in float32."""
    args = operands(length, sizes=KERNEL_SIZES)
    on_kernels()
    whole = jax.jit(lambda *v: ssm.ssd_scan(*v, chunk=256))(*args)
    got = jax.jit(lambda *v: ssm.ssd_scan(*v, chunk=128))(*args)
    assert gap(got, whole) < 5e-5


def spliced(scan, length, cut, sizes):
    """``scan`` of a row, and of the row with everything from ``cut`` on
    replaced."""
    xs, dt, a, b, c, d = operands(length, seed=3, sizes=sizes)
    other = operands(length, seed=4, sizes=sizes)
    splice = lambda x, y: jnp.concatenate([x[:, :cut], y[:, cut:]], axis=1)  # noqa: E731
    changed = scan(
        splice(xs, other[0]), splice(dt, other[1]), a,
        splice(b, other[3]), splice(c, other[4]), d,
    )
    return scan(xs, dt, a, b, c, d), changed


def test_a_row_s_output_hangs_on_nothing_after_it():
    cut = 13
    same, changed = spliced(jax.jit(lambda *v: ssm.ssd_scan(*v, chunk=8)), 27, cut, (H, P, N))
    assert bool(jnp.all(changed[:, :cut] == same[:, :cut]))
    assert gap(changed[:, cut:], same[:, cut:]) > 1e-2
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 27, 6))
    w = jax.random.normal(jax.random.PRNGKey(6), (6, 4))
    bias = jnp.zeros((6,))
    y = ssm.causal_conv1d(x, w, bias)
    later = ssm.causal_conv1d(x.at[:, cut:].set(7.0), w, bias)
    assert bool(jnp.all(later[:, :cut] == y[:, :cut]))


def test_a_row_s_output_from_the_kernels_hangs_on_nothing_after_it(on_kernels):
    """The cut inside the second of three chunks, off a tile's edge."""
    on_kernels()
    cut = 200
    same, changed = spliced(
        jax.jit(lambda *v: ssm.ssd_scan(*v, chunk=128)), 384, cut, KERNEL_SIZES
    )
    assert bool(jnp.all(changed[:, :cut] == same[:, :cut]))
    assert gap(changed[:, cut:], same[:, cut:]) > 1e-2


def test_a_step_of_nought_neither_decays_nor_writes():
    """What pads a ragged row: the state passes a ``Δ = 0`` position
    unchanged, so the positions after a stretch of them read what they
    would have read without it."""
    xs, dt, a, b, c, d = operands(16, seed=7)
    hole = dt.at[:, 4:12].set(0.0)
    scan = jax.jit(lambda *v: ssm.ssd_scan(*v, chunk=8))
    with_hole = scan(xs, hole, a, b, c, d)
    keep = np.r_[0:4, 12:16]
    without = scan(xs[:, keep], dt[:, keep], a, b[:, keep], c[:, keep], d)
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


def labels_of(name):
    seen = [e for e in obs.get_bus().ring if e.get("name") == name]
    assert len(seen) == 1, (name, len(seen))
    return seen[0]["labels"] if "labels" in seen[0] else seen[0]


def test_the_scan_counts_what_it_chose():
    obs.reset()
    ssm.ssd_scan(*operands(27), chunk=8)
    labels = labels_of("ssm.impl.xla")
    assert labels["chunks"] == 4 and labels["padded"] == 5 and labels["chunk"] == 8
    assert labels["heads"] == H and labels["state"] == N and labels["head_dim"] == P
    assert labels["head_block"] == 0
    obs.reset()


@pytest.mark.parametrize(
    "case,shape,state,chunk,groups,initializing,backend,want",
    [
        # the benchmark's cell: 64 heads of 64, a state of 128, chunks of 256
        ("the-cell", (2, 4096, 64, 64), 128, 256, 1, False, "tpu", "pallas"),
        ("a-ragged-row", (1, 300, 16, 64), 128, 128, 2, False, "tpu", "pallas"),
        ("the-cpu", (2, 4096, 64, 64), 128, 256, 1, False, "cpu", "xla"),
        ("the-weight-draw", (2, 4096, 64, 64), 128, 256, 1, True, "tpu", "xla"),
        ("granite-tiny", (2, 32, 4, 16), 16, 8, 1, False, "tpu", "xla"),
        ("a-short-row", (2, 27, 64, 64), 128, 256, 1, False, "tpu", "xla"),
        ("a-narrow-state", (2, 512, 64, 64), 64, 256, 1, False, "tpu", "xla"),
        ("too-few-heads-a-group", (2, 512, 16, 64), 128, 256, 4, False, "tpu", "xla"),
    ],
)
def test_the_rule_takes_the_kernels_where_they_are_safe_and_the_shapes_tile(
    monkeypatch, case, shape, state, chunk, groups, initializing, backend, want
):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    obs.reset()
    xs = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    got = jax.eval_shape(
        lambda x: jnp.zeros(()) * 0 + (ssm.resolve_impl(
            x, state=state, chunk=chunk, groups=groups, initializing=initializing
        ) == "pallas"), xs,
    )
    assert got.shape == ()
    labels = labels_of(f"ssm.impl.{want}")
    q = min(chunk, shape[1])
    assert labels["shape"] == list(shape) and labels["chunk"] == q
    assert labels["chunks"] == -(-shape[1] // q) and labels["padded"] == -shape[1] % q
    assert labels["head_block"] == (8 if want == "pallas" else 0)
    obs.reset()


def test_several_devices_under_gspmd_keep_the_xla_form(monkeypatch):
    """Operands with no varying axes on a world of several devices (the
    pjit engine): a custom call would force replication."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.device_count() > 1
    xs = jnp.zeros((1, 256, 16, 64), jnp.bfloat16)
    assert ssm.resolve_impl(xs, state=128, chunk=128) == "xla"


def test_a_traced_backward_of_the_kernels_is_counted(on_kernels):
    on_kernels()
    obs.reset()
    args = operands(128, batch=1, sizes=KERNEL_SIZES)
    jax.jit(jax.grad(lambda *v: jnp.sum(ssm.ssd_scan(*v, chunk=128))))(*args)
    labels = labels_of("ssm.bwd.pallas")
    assert labels["head_block"] == 8 and labels["chunks"] == 1 and labels["chunk"] == 128
    obs.reset()


def test_heads_that_do_not_divide_into_groups_are_refused():
    xs, dt, a, b, c, d = operands(8)
    with pytest.raises(ValueError, match="groups"):
        ssm.ssd_scan(xs, dt, a, jnp.tile(b, (1, 1, 3, 1)), jnp.tile(c, (1, 1, 3, 1)), d, chunk=8)
