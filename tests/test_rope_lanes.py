"""``ops/pallas/rope_lanes``: rotary positions turned in place on each
head's last lanes of ``q [B, T, H·d]``, against ``models/decoder.rotary``
over the same lanes viewed as heads. The kernels run in interpret mode
here, over several row blocks, so that a table block taken for the wrong
rows, a partner lane taken the wrong way or a head's block read off by
one shows as a gap of the rotation's own order."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.models.decoder import rotary
from distributeddeeplearning_tpu.ops.pallas import rope_lanes as rl

THETA = 10_000.0


def _reference(q, positions, heads, rope):
    """``rotary`` over each head's last ``rope`` lanes, the rest as it was."""
    b, t, width = q.shape
    x = q.reshape(b, t, heads, width // heads)
    nope = x.shape[-1] - rope
    turned = rotary(x[..., nope:], positions, THETA).astype(q.dtype)
    return jnp.concatenate([x[..., :nope], turned], axis=-1).reshape(q.shape)


# (rows, tokens, heads, head_dim, rope): the published 256 / 64 (a head's
# last 128-lane block), a whole head of rotary lanes, a rotary part inside
# one block, and a head off the lanes (the whole row a block)
SHAPES = [
    (2, 80, 3, 256, 64),
    (1, 64, 2, 128, 128),
    (2, 48, 4, 128, 16),
    (2, 48, 3, 32, 8),
]


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "b{}_t{}_h{}_d{}_r{}".format(*s))
def test_rope_lanes_turns_each_head_s_last_lanes_as_rotary_does(shape, impl):
    b, t, heads, head_dim, rope = shape
    q = jax.random.normal(jax.random.PRNGKey(0), (b, t, heads * head_dim), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(1), q.shape, jnp.float32)
    positions = jnp.arange(t) + 3

    def turned(q):
        return rl.rope_lanes(q, positions, heads=heads, rope=rope, theta=THETA, impl=impl)

    got, vjp = jax.vjp(turned, q)
    want, vjp_want = jax.vjp(lambda q: _reference(q, positions, heads, rope), q)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(vjp(g)[0], vjp_want(g)[0], atol=2e-6, rtol=2e-6)
    # the lanes before a head's rotary part are left as the product wrote them
    nope = (np.arange(heads * head_dim) % head_dim) < head_dim - rope
    assert np.array_equal(np.asarray(got)[..., nope], np.asarray(q)[..., nope])
    # other positions are another rotation
    assert np.abs(np.asarray(got) - np.asarray(_reference(q, positions + 1, heads, rope))).max() > 1e-2


def test_rope_lanes_keeps_bfloat16_and_rounds_once():
    """In bfloat16 the rotation is made in float32 and rounded once, as
    ``rotary``'s result is cast by its caller."""
    q = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 2 * 256), jnp.bfloat16)
    positions = jnp.arange(64)
    got = rl.rope_lanes(q, positions, heads=2, rope=64, theta=THETA)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(  # at most the one rounding apart (a unit in the last place)
        np.asarray(got, np.float32), np.asarray(_reference(q, positions, 2, 64), np.float32),
        rtol=2.0 ** -8, atol=2.0 ** -16,
    )


@pytest.mark.parametrize(
    "heads,head_dim,rope,width", [(20, 256, 64, 128), (2, 128, 128, 128), (3, 32, 8, 96)]
)
def test_a_block_holds_a_head_s_rotary_lanes(heads, head_dim, rope, width):
    assert rl.block_width(heads, head_dim, rope) == width


@pytest.mark.parametrize("heads,rope", [(3, 7), (5, 8), (2, 0), (2, 66)])
def test_a_rotary_part_that_does_not_fit_a_head_is_refused(heads, rope):
    with pytest.raises(ValueError, match="no rotary part"):
        rl.rope_lanes(jnp.zeros((1, 16, 64)), jnp.arange(16), heads=heads, rope=rope, theta=THETA)
