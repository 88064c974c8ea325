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
