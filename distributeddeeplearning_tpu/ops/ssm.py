"""State-space sequence mixing: the Mamba-2 scan and the causal
depthwise convolution that feeds it.

The models' other mixer is softmax attention (``ops/attention.py``),
whose cost a token grows with the row. This one carries a state instead:
a head ``h`` of width ``P`` keeps ``S ∈ R^{P×N}`` and, with a step
``Δ_{t,h} > 0`` and a decay rate ``a_h < 0``,

    ``S_t = exp(Δ_{t,h} a_h) · S_{t−1} + Δ_{t,h} · xs_{t,h} ⊗ B_t``,
    ``y_{t,h} = S_t · C_t + D_h · xs_{t,h}``,   ``S_0 = 0``,

``B_t, C_t ∈ R^N`` shared by the ``H // G`` heads of a group (Mamba-2's
"state-space duality" layer, arXiv:2405.21060). :func:`ssd_scan`
computes it **in chunks** of ``Q`` positions. With ``c_t`` the running
sum of ``Δ_s a`` from the chunk's start and ``L_{t,s} = exp(c_t − c_s)``
for ``s ≤ t`` (0 above the diagonal), a chunk's four products are

* ``C·Bᵀ`` ``[Q, Q]`` a group;
* ``Y_intra = ((C·Bᵀ) ⊙ L)·(Δ ⊙ xs)``: what the chunk's own positions
  give each other;
* ``Y_inter,t = exp(c_t) · S_{k−1}·C_t``: what the state carried in gives;
* ``R_k = Σ_s exp(c_Q − c_s) Δ_s xs_s ⊗ B_s``, the chunk's own state,
  and ``S_k = exp(c_Q) S_{k−1} + R_k`` goes on to the next chunk.

The chunks of a row are walked by a ``lax.scan`` that carries ``S`` in
float32, its body under ``jax.checkpoint``: **one chunk's ``[H, Q, Q]``
decay matrix is live at a time**, forward and backward (all ``T/Q`` of
them at once are 0.25 GiB a tensor at 4,096 tokens, 64 heads and Q =
256), and the backward pass is the reverse scan over the chunks that
carries ``dS`` and recomputes a chunk's ``L`` from the ``S_{k−1}`` the
forward kept (2 MiB a chunk). Products run in the operands' dtype with
float32 accumulation; ``c_t``, ``L``, ``Δ`` and the state are float32.
A row that is no whole number of chunks is padded with ``Δ = 0``, under
which a position neither decays the state nor writes into it.

:func:`causal_conv1d` is the depthwise convolution over the sequence
that Mamba-2 puts before the scan: channel ``c`` at position ``t`` reads
its own last ``K`` values, noughts before the row's start.

Which lowering a call takes is this module's rule, as ``ops/attention.
resolve_impl`` is the attention core's: :func:`resolve_impl`. Today
there is one, XLA's (``"xla"``); a kernel for the intra-chunk products
would be chosen here, from what the call can see, and nowhere else.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu import obs

# Scopes of the two operations inside a state-space mixer (the module
# that holds them is named `SSM`): `models/decoder.SSM_GROUPS` reads them.
SSM = "ssm"
SSM_CONV = "ssm_conv"
SSM_SCAN = "ssm_scan"


def resolve_impl(xs, *, state: int, chunk: int) -> str:
    """The scan's lowering for one call, chosen from what the call can
    see (``xs [B, T, H, P]``), and counted at trace time:
    ``ssm.impl.<path>`` with the labels ``shape``, ``heads``,
    ``head_dim``, ``state``, ``chunk``, ``chunks`` (a row's) and
    ``padded`` (positions added to fill the last chunk). The XLA form is
    the only one there is."""
    b, t, h, p = xs.shape
    q = min(chunk, t)
    chunks = -(-t // q)
    obs.counter(
        "ssm.impl.xla", shape=[b, t, h, p], heads=h, head_dim=p, state=state,
        chunk=q, chunks=chunks, padded=chunks * q - t,
    )
    return "xla"


def causal_conv1d(x, w, bias):
    """``y[b, t, c] = bias[c] + Σ_j w[c, j] · x[b, t − (K−1) + j, c]``
    over ``x [B, T, C]``, ``w [C, K]``, noughts before the row's start:
    tap ``K − 1`` reads the position itself. Multiply-adds in float32,
    the result in ``x``'s dtype."""
    k = w.shape[1]
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = bias.astype(jnp.float32)
    for j in range(k):
        y = y + w[:, j].astype(jnp.float32) * padded[:, j:j + t].astype(jnp.float32)
    return y.astype(x.dtype)


def _chunk(state, inputs, *, a, groups: int):
    """One chunk: ``state [B, H, P, N]`` float32 in, the chunk's ``y``
    (less the ``D`` term) and the state after it out. The heads of a
    group ride on an axis of their own (``H = G x R``), so that the
    products with ``B`` and ``C`` read the group's one copy."""
    xs, dt, b, c = inputs  # [B,Q,H,P], [B,Q,H] float32, [B,Q,G,N] x 2
    dtype = xs.dtype
    f32 = jnp.float32
    batch, q, h, p = xs.shape
    g, r = groups, h // groups
    cum = jnp.cumsum(dt * a, axis=1)  # c_t  [B,Q,H], <= 0 and falling
    cum_h = cum.transpose(0, 2, 1)  # [B,H,Q]
    seg = cum_h[:, :, :, None] - cum_h[:, :, None, :]  # c_t - c_s  [B,H,Q,Q]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))  # L
    cb = jnp.einsum("btgn,bsgn->bgts", c, b, preferred_element_type=f32)
    weights = (cb[:, :, None] * decay.reshape(batch, g, r, q, q)).astype(dtype)
    xdt = xs.astype(f32) * dt[..., None]  # Δ ⊙ xs
    y = jnp.einsum(
        "bgrts,bsgrp->btgrp", weights, xdt.astype(dtype).reshape(batch, q, g, r, p),
        preferred_element_type=f32,
    )
    # what the state carried in gives, faded by each position's c_t
    carried = jnp.einsum(
        "bgrpn,btgn->btgrp", state.astype(dtype).reshape(batch, g, r, p, -1), c,
        preferred_element_type=f32,
    )
    y = y.reshape(batch, q, h, p) + jnp.exp(cum)[..., None] * carried.reshape(
        batch, q, h, p
    )
    # the chunk's own state, each position faded to the chunk's end
    last = cum[:, -1:, :]  # c_Q  [B,1,H]
    faded = (xdt * jnp.exp(last - cum)[..., None]).astype(dtype)
    own = jnp.einsum(
        "bsgrp,bsgn->bgrpn", faded.reshape(batch, q, g, r, p), b,
        preferred_element_type=f32,
    ).reshape(state.shape)
    state = jnp.exp(last[:, 0])[:, :, None, None] * state + own
    return state, y.astype(dtype)


def ssd_scan(xs, dt, a, b, c, d, *, chunk: int):
    """The recurrence at the top, in chunks: ``xs [B, T, H, P]``, ``dt
    [B, T, H]`` (the step ``Δ``, positive), ``a [H]`` (negative), ``b``,
    ``c`` ``[B, T, G, N]``, ``d [H]`` -> ``y [B, T, H, P]`` in ``xs``'s
    dtype. Differentiable in all six."""
    batch, t, h, p = xs.shape
    g, n = b.shape[2], b.shape[3]
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    resolve_impl(xs, state=n, chunk=chunk)
    q = min(chunk, t)
    chunks = -(-t // q)
    dt = dt.astype(jnp.float32)
    a = a.astype(jnp.float32)
    pad = chunks * q - t

    def by_chunk(x):  # [B, T, ...] -> [chunks, B, Q, ...]
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((batch, chunks, q) + x.shape[2:])
        return jnp.moveaxis(x, 1, 0)

    body = jax.checkpoint(
        lambda state, inputs: _chunk(state, inputs, a=a, groups=g)
    )
    first = jnp.zeros((batch, h, p, n), jnp.float32)
    vma = tuple(sorted({
        axis for v in (xs, dt, a, b, c) for axis in getattr(jax.typeof(v), "vma", ())
    }))
    if vma:  # inside shard_map the carry varies as what is added to it does
        first = jax.lax.pcast(first, vma, to="varying")
    _, y = jax.lax.scan(
        body, first, (by_chunk(xs), by_chunk(dt), by_chunk(b), by_chunk(c)),
    )
    y = jnp.moveaxis(y, 0, 1).reshape(batch, chunks * q, h, p)[:, :t]
    return (
        y.astype(jnp.float32) + d.astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
    ).astype(xs.dtype)
