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
resolve_impl`` is the attention core's: :func:`resolve_impl`. There are
two of the one algorithm: XLA's (``"xla"``, the ``lax.scan`` above: the
CPU's path, the weight draw's, short rows' and odd sizes', and what the
kernels are tested against) and the Pallas kernels of
``ops/pallas/ssd.py`` (``"pallas"``), which make, use and drop a chunk's
decay matrix in VMEM and keep the state as it entered each chunk for
their backward. The choice is made here, from what the call can see, and
nowhere else.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.ops.attention import custom_call_is_safe
from distributeddeeplearning_tpu.ops.pallas import ssd

# Scopes of the two operations inside a state-space mixer (the module
# that holds them is named `SSM`): `models/decoder.SSM_GROUPS` reads them.
SSM = "ssm"
SSM_CONV = "ssm_conv"
SSM_SCAN = "ssm_scan"


def resolve_impl(xs, *, state: int, chunk: int, groups: int = 1,
                 initializing: bool = False) -> str:
    """The scan's lowering for one call, chosen from what the call can
    see (``xs [B, T, H, P]``): the kernels (``"pallas"``) where a custom
    call is safe (``ops/attention.custom_call_is_safe``: a TPU, local
    operands, not the weight draw) and the shapes are the kernels'
    (``ops/pallas/ssd.supports``: a chunk of 128 or 256, heads of 32, 64
    or 128, a state of whole lanes, a group's heads in whole blocks of
    8); the XLA form otherwise. Counted at trace time:
    ``ssm.impl.<path>`` with the labels ``shape``, ``heads``,
    ``head_dim``, ``state``, ``chunk``, ``chunks`` (a row's), ``padded``
    (positions added to fill the last chunk) and ``head_block`` (heads a
    kernel program; 0 under XLA)."""
    b, t, h, p = xs.shape
    q = min(chunk, t)
    chunks = -(-t // q)
    impl, hb = "xla", 0
    if custom_call_is_safe(xs, initializing) and ssd.supports(q, h, groups, p, state):
        impl, hb = "pallas", ssd.HEAD_BLOCK
    obs.counter(
        f"ssm.impl.{impl}", shape=[b, t, h, p], heads=h, head_dim=p, state=state,
        chunk=q, chunks=chunks, padded=chunks * q - t, head_block=hb,
    )
    return impl


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


def _xla_scan(xs, dt, b, c, a, *, q: int, chunks: int):
    """The chunks of a padded row walked by a ``lax.scan``: ``y`` less
    the ``D`` term, ``[B, chunks·Q, H, P]``."""
    batch, _, h, p = xs.shape
    g, n = b.shape[2], b.shape[3]

    def by_chunk(x):  # [B, T, ...] -> [chunks, B, Q, ...]
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
    return jnp.moveaxis(y, 0, 1).reshape(batch, chunks * q, h, p)


def _kernel_scan(xs, dt, b, c, a, *, q: int, chunks: int):
    """The same by ``ops/pallas/ssd.py``'s kernels, which take the
    running sum ``c_t`` of ``Δ·a`` within each chunk from here: autodiff
    carries its cotangent back to ``Δ`` and ``a``."""
    batch, t, h, _ = xs.shape
    cum = jnp.cumsum((dt * a).reshape(batch, chunks, q, h), axis=2)
    return ssd.ssd_chunks(xs, dt, cum.reshape(batch, t, h), b, c, chunk=q)


def ssd_scan(xs, dt, a, b, c, d, *, chunk: int, initializing: bool = False):
    """The recurrence at the top, in chunks: ``xs [B, T, H, P]``, ``dt
    [B, T, H]`` (the step ``Δ``, positive), ``a [H]`` (negative), ``b``,
    ``c`` ``[B, T, G, N]``, ``d [H]`` -> ``y [B, T, H, P]`` in ``xs``'s
    dtype. Differentiable in all six. ``initializing``: the caller's
    statement that this is the weight draw (:func:`resolve_impl`)."""
    batch, t, h, p = xs.shape
    g, n = b.shape[2], b.shape[3]
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    impl = resolve_impl(xs, state=n, chunk=chunk, groups=g, initializing=initializing)
    q = min(chunk, t)
    chunks = -(-t // q)
    dt = dt.astype(jnp.float32)
    a = a.astype(jnp.float32)
    operands = xs, dt, b, c
    if chunks * q != t:  # Δ = 0: neither decays the state nor writes into it
        operands = tuple(
            jnp.pad(x, ((0, 0), (0, chunks * q - t)) + ((0, 0),) * (x.ndim - 2))
            for x in operands
        )
    scan = _kernel_scan if impl == "pallas" else _xla_scan
    y = scan(*operands, a, q=q, chunks=chunks)[:, :t]
    return (
        y.astype(jnp.float32) + d.astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
    ).astype(xs.dtype)
