"""Attention ops with pluggable implementations.

The reference has no attention anywhere (vision-only, SURVEY.md §2b) —
this op layer exists because the BASELINE.json configs add ViT-B/16 and
because long-context support is first-class in this framework. One
signature, three implementations:

* ``xla``   — einsum softmax attention: the ``[T, T]`` scores and the
  softmax weights go through HBM, forward and backward.
* ``pallas`` — flash-attention TPU kernels (``ops/pallas/flash.py``):
  nothing of size ``T × T`` leaves the chip. Ahead of the einsum from
  T = 640 on (v5e, d = 64; ``flash.supports``), and the only way a long
  context fits.
* ``ring``  — sequence-parallel blockwise attention over a ``seq`` mesh
  axis (``parallel/ring_attention.py``): K/V blocks rotate around the
  ring via ``ppermute`` while each shard holds only T/n of the sequence.

:func:`block_diffusion_attention` is the core of the block-diffusion
training objective (a row runs as ``[noised ‖ clean]`` under a
block-granular mask, grouped query heads): the einsum over the dense
mask, or three passes of the flash kernels and a merge by logsumexp.

All take ``[batch, seq, heads, head_dim]`` (BTHD) tensors. A caller
names one; ``models/vit.Attention`` with ``attn_impl="auto"`` (the
models' default) picks by shape and platform, and takes the packed
small-T kernel (``ops/pallas/flash_packed.py``) before this layer.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _xla_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def dot_product_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    impl: str = "xla",
    axis_name: Optional[str] = None,
) -> jnp.ndarray:
    """Multi-head attention over BTHD tensors.

    ``impl='ring'`` requires running inside ``shard_map`` with the
    sequence dimension sharded over ``axis_name`` (default: the mesh
    convention's ``"seq"`` axis, ``parallel/mesh.py``).
    """
    if impl == "xla":
        return _xla_attention(q, k, v, causal=causal, scale=scale)
    if impl == "pallas":
        from distributeddeeplearning_tpu.ops.pallas.flash import flash_attention

        return flash_attention(q, k, v, causal=causal, scale=scale)
    if impl == "ring":
        axis_name = axis_name or "seq"
        from distributeddeeplearning_tpu.parallel.ring_attention import (
            ring_attention,
        )

        return ring_attention(q, k, v, axis_name=axis_name, causal=causal, scale=scale)
    raise ValueError(f"unknown attention impl {impl!r}")


def block_diffusion_mask(length: int, block_len: int) -> jnp.ndarray:
    """``[2L, 2L]`` bool, True where a query may see a key, over a row
    laid out ``[noised ‖ clean]`` with positions ``[0..L−1, 0..L−1]`` in
    blocks of ``block_len`` (BD3-LM's training mask): noised → noised in
    the query's own block; noised → clean in the blocks before it; clean
    → clean up to and with its own block; clean → noised never."""
    beta = jnp.arange(length) // block_len
    q, k = beta[:, None], beta[None, :]
    return jnp.block([[q == k, k < q], [jnp.zeros_like(q == k), k <= q]])


def _grouped(q, kv_heads: int):
    """``[B, T, H, d]`` queries as ``[B, T, KV, H // KV, d]``."""
    b, t, h, d = q.shape
    return q.reshape(b, t, kv_heads, h // kv_heads, d)


def _xla_block_diffusion(q, k, v, block_len: int, scale: float):
    b, t2, h, d = q.shape
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", _grouped(q, k.shape[2]), k) * scale
    mask = block_diffusion_mask(t2 // 2, block_len)
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", weights, v).reshape(b, t2, h, d)


def _flash_block_diffusion(q, k, v, block_len: int, scale: float):
    """Three passes of the flash kernels and no ``[2L, 2L]`` tensor: the
    clean queries over the clean keys (blocks ≤ their own) and the
    noised queries over the clean keys (blocks < their own) are
    block-causal passes, tiles above the diagonal skipped; the noised
    queries over the noised keys of their own block are one diagonal
    tile a program. The last two are merged by logsumexp (a query of the
    first block saw no clean key: that pass comes with a logsumexp of
    −1e30 there and weighs nothing)."""
    from distributeddeeplearning_tpu.ops.pallas.flash import (
        Mask,
        flash_attention_stats,
    )

    half = q.shape[1] // 2
    (qn, qc), (kn, kc), (vn, vc) = (
        (x[:, :half], x[:, half:]) for x in (q, k, v)
    )
    clean, _ = flash_attention_stats(
        qc, kc, vc, mask=Mask(True, block_len), scale=scale
    )
    before, lse_before = flash_attention_stats(
        qn, kc, vc, mask=Mask(True, block_len, strict=True), scale=scale
    )
    own, lse_own = flash_attention_stats(
        qn, kn, vn, mask=Mask(False, block_len, own=True), scale=scale
    )
    lse = jnp.logaddexp(lse_before, lse_own)
    noised = (
        jnp.exp(lse_before - lse)[..., None] * before.astype(jnp.float32)
        + jnp.exp(lse_own - lse)[..., None] * own.astype(jnp.float32)
    )
    return jnp.concatenate([noised.astype(q.dtype), clean], axis=1)


def block_diffusion_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    block_len: int,
    scale: Optional[float] = None,
    impl: str = "xla",
) -> jnp.ndarray:
    """Attention of a ``[noised ‖ clean]`` row under
    :func:`block_diffusion_mask`: ``q [B, 2L, H, d]`` against ``k``,
    ``v`` ``[B, 2L, KV, d]``, ``H // KV`` query heads to a key head.
    ``impl``: ``"xla"`` (einsum over the dense mask) or ``"pallas"``
    (the flash kernels; nothing of size ``L × L`` leaves the chip)."""
    if q.shape[1] % (2 * block_len):
        raise ValueError(
            f"{q.shape[1]} positions are no two halves of {block_len}-blocks"
        )
    scale = float(scale if scale is not None else q.shape[-1] ** -0.5)
    if impl == "xla":
        return _xla_block_diffusion(q, k, v, block_len, scale)
    if impl == "pallas":
        return _flash_block_diffusion(q, k, v, block_len, scale)
    raise ValueError(f"unknown block-diffusion attention impl {impl!r}")
