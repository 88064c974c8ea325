"""Rotary positions turned in place on the lanes of a product's output:
``q [B, T, H·d]``, each head's last ``r`` lanes rotated as ``rotate_half``
does (``models/decoder.rotary``: ``[x1 | x2] -> [x1·cos − x2·sin | x2·cos +
x1·sin]``, float32, rounded once to ``q``'s dtype), every other lane left
where the product wrote it. Latent attention's queries
(``models/decoder.MlaAttention``) come so from ``q_b``, as the flash
kernels read them.

XLA does the partner of a lane (half a rotary part away) as a roll of the
whole ``[.., H·d]`` row, and makes of it float32 copies of q through HBM
that cost several times the product (v5e's own estimate, compiled for a
described chip). These kernels read and write only the 128-lane block of
each head that holds its rotary lanes (the last, where ``d`` is a whole
number of 128 lanes and ``r`` at most 128), with the output aliased to
the input: a partner is a lane rotation of that block (``pltpu.roll``).
Where ``d`` is not so a block is a whole row, all heads. The backward is
the same kernel transposed: ``dx = dy·C + P(dy·S)``.

On a backend that is not a TPU the kernels run in Pallas interpreter mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.ops.pallas.flash import _vma

LANES = 128
_ROWS = (2048, 1024, 512, 256, 128, 64, 32, 16)  # rows a program, the first that tiles T


def block_width(heads: int, head_dim: int, rope: int) -> int:
    """The lanes a program reads of a row: the last 128 of a head where
    they hold all its rotary lanes, else the whole row."""
    if head_dim % LANES == 0 and rope <= LANES:
        return LANES
    return heads * head_dim


def tables(positions, head_dim: int, rope: int, width: int, theta: float):
    """``C``, ``S`` ``[T, width]`` float32 for a block of ``width`` lanes
    whose heads (of ``period = min(width, head_dim)`` lanes within it)
    end in their rotary lanes: ``C`` 1 and ``S`` 0 on the other lanes,
    ``[cos | cos]`` and ``[−sin | sin]`` on the rotary ones (``rotary``'s
    angles to the bit)."""
    half = rope // 2
    period = min(width, head_dim)
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]  # [T, r/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    t = angle.shape[0]
    ones = jnp.ones((t, period - rope), jnp.float32)
    reps = width // period
    return (
        jnp.concatenate([ones, cos, cos] * reps, axis=-1),
        jnp.concatenate([jnp.zeros_like(ones), -sin, sin] * reps, axis=-1),
    )


def _kernel(c_ref, s_ref, q_ref, o_ref, *, period: int, rope: int, transpose: bool):
    x = q_ref[...].astype(jnp.float32)
    c, s = c_ref[...], s_ref[...]
    w, half = x.shape[1], rope // 2
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1) % period

    def partner(y):  # a rotary lane's: y[L + r/2] in the first half, y[L − r/2] in the second
        return jnp.where(
            lane < period - half, pltpu.roll(y, w - half, 1), pltpu.roll(y, half, 1)
        )

    if transpose:  # P is its own transpose on the rotary lanes; S is 0 on the rest
        out = x * c + jnp.where(lane >= period - rope, partner(x * s), 0.0)
    else:
        out = x * c + partner(x) * s
    o_ref[...] = out.astype(o_ref.dtype)


def _row_block(t: int) -> int:
    return next((r for r in _ROWS if t % r == 0), t)


@functools.partial(
    jax.jit, static_argnames=("heads", "head_dim", "rope", "transpose", "interpret")
)
def _call(q, c, s, *, heads, head_dim, rope, transpose, interpret):
    n = q.shape[0]
    t, w = c.shape
    tb = _row_block(t)
    last = head_dim // w - 1 if w <= head_dim else 0  # the block of a head that is read
    per_head = max(head_dim // w, 1)
    q_spec = pl.BlockSpec((tb, w), lambda i, h: (i, h * per_head + last))
    table = pl.BlockSpec((tb, w), lambda i, h: (i % (t // tb), 0))
    return pl.pallas_call(
        functools.partial(
            _kernel, period=min(w, head_dim), rope=rope, transpose=transpose
        ),
        grid=(n // tb, heads if w <= head_dim else 1),
        in_specs=[table, table, q_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype, vma=_vma(q, c, s)),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name="rope_lanes_bwd" if transpose else "rope_lanes",
    )(c, s, q)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _rope(q, c, s, heads, head_dim, rope, interpret):
    return _call(
        q, c, s, heads=heads, head_dim=head_dim, rope=rope, transpose=False,
        interpret=interpret,
    )


def _rope_fwd(q, c, s, heads, head_dim, rope, interpret):
    return _rope(q, c, s, heads, head_dim, rope, interpret), (c, s)


def _rope_bwd(heads, head_dim, rope, interpret, res, dy):
    c, s = res
    dq = _call(
        dy, c, s, heads=heads, head_dim=head_dim, rope=rope, transpose=True,
        interpret=interpret,
    )
    return dq, None, None


_rope.defvjp(_rope_fwd, _rope_bwd)


def rope_lanes(
    q, positions, *, heads: int, rope: int, theta: float, impl: str = "pallas",
):
    """``q [B, T, H·d]`` with each head's last ``rope`` lanes turned by
    the rotary angles of ``positions [T]`` (base ``theta``), in ``q``'s
    dtype; differentiable in ``q``. ``impl="xla"``: the same arithmetic
    as XLA's elementwise work over the whole row, the partner a roll of
    it (where no kernel may stand: off the chip, at the weight draw)."""
    b, t, width = q.shape
    head_dim = width // heads
    if width % heads or rope % 2 or not 0 < rope <= head_dim:
        raise ValueError(f"no rotary part of {rope} lanes in {heads} heads of {q.shape}")
    if impl == "xla":
        c, s = tables(positions, head_dim, rope, width, theta)
        x, half = q.astype(jnp.float32), rope // 2
        first = jnp.arange(width) % head_dim < head_dim - half
        partner = jnp.where(first, jnp.roll(x, -half, axis=-1), jnp.roll(x, half, axis=-1))
        return (x * c + partner * s).astype(q.dtype)
    interpret = jax.default_backend() != "tpu"
    w = block_width(heads, head_dim, rope)
    obs.counter("rope_lanes.pallas", shape=list(q.shape), heads=heads, rope=rope, block=w)
    c, s = tables(positions, head_dim, rope, w, theta)
    out = _rope(q.reshape(b * t, width), c, s, heads, head_dim, rope, interpret)
    return out.reshape(q.shape)
