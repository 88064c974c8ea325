"""Attention ops with pluggable implementations.

The reference has no attention anywhere (vision-only, SURVEY.md §2b) —
this op layer exists because the BASELINE.json configs add ViT-B/16 and
because long-context support is first-class in this framework. One
signature (:func:`dot_product_attention`: every key, the causal rule, or
the causal rule within a window of the last keys), three
implementations:

* ``xla``   — einsum softmax attention: the ``[T, T]`` scores and the
  softmax weights go through HBM, forward and backward.
* ``pallas`` — flash-attention TPU kernels (``ops/pallas/flash.py``):
  nothing of size ``T × T`` leaves the chip, and blocks the mask rules
  out (above the diagonal, behind the window) are neither computed nor
  fetched. Ahead of the einsum from T = 640 on (v5e, d = 64;
  ``flash.supports``), and the only way a long context fits.
* ``ring``  — sequence-parallel blockwise attention over a ``seq`` mesh
  axis (``parallel/ring_attention.py``): K/V blocks rotate around the
  ring via ``ppermute`` while each shard holds only T/n of the sequence
  (full or causal; no window).

:func:`block_diffusion_attention` is a fourth mask with an entry of its
own, the core of the block-diffusion training objective (a row runs as
``[noised ‖ clean]`` under a block-granular mask, grouped query heads):
the einsum over the dense mask, or three passes of the flash kernels and
a merge by logsumexp.

All take ``[batch, seq, heads, head_dim]`` (BTHD) tensors; keys and
values may have fewer heads than the queries (grouped queries: ``H //
KV`` query heads to a key head).

**Which of them a call runs is decided here**, in :func:`resolve_impl`:
a model states what it was asked (``attn_impl``; ``"auto"`` is the
models' default), what it can see of the call (operands, head geometry,
whether it is initializing) and whether a fused ``[B, T, 3·H·d]``
projection feeds the core (only then can the packed small-T kernel,
``ops/pallas/flash_packed.py``, be taken, and the model calls it
itself). :func:`kernel_interpreted` answers the engines' question about
a value of ``attn_impl``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu import obs

# Values of ``impl`` whose core is a Pallas kernel: the streaming flash
# kernels, the packed small-T kernel.
_KERNEL_IMPLS = ("pallas", "fused")

# What a flash forward writes and its backward reads besides q, k, v (the
# output and the rows' logsumexp), under the names a remat policy can
# keep (`jax.checkpoint_policies.save_only_these_names`): dear to make
# again (the forward kernel) and small to hold. `ops/pallas/flash.py`
# names them; `models/decoder.SpecDecoder`'s block remat keeps them.
ATTN_OUT, ATTN_LSE = "attn_out", "attn_lse"


def kernel_interpreted(impl: Optional[str]) -> bool:
    """Whether a model built with ``attn_impl=impl`` would run a Pallas
    kernel in interpret mode (off the TPU): the HLO interpreter's
    internal slicing trips ``shard_map``'s varying-axes checker
    (upstream limitation; its own error message recommends
    ``check_vma=False``), so the engines drop the check for exactly this
    case. ``"auto"`` takes a kernel on a TPU alone, so it needs no
    exception."""
    return impl in _KERNEL_IMPLS and jax.default_backend() != "tpu"


def custom_call_is_safe(x, initializing: bool) -> bool:
    """Whether a Pallas kernel may take ``x`` in this call: on a TPU,
    operands that are already local (one device, or inside
    ``shard_map``: the dp/sp engines; under multi-device GSPMD, the pjit
    engine, operands carry no varying axes and a custom call would force
    replication), and not while initializing (parameters do not depend
    on the path, and the weight draw should lower no kernel it never
    runs). The state-space scan's rule (``ops/ssm.resolve_impl``) asks
    the same."""
    local = bool(getattr(jax.typeof(x), "vma", ())) or jax.device_count() == 1
    return jax.default_backend() == "tpu" and local and not initializing


def kernel_is_safe(x, initializing: bool) -> bool:
    """Whether a Pallas kernel may stand in this call's attention core:
    ``[B, T, D]`` operands where :func:`custom_call_is_safe` holds."""
    return x.ndim == 3 and custom_call_is_safe(x, initializing)


def resolve_impl(
    asked: str,
    x,
    *,
    heads: int,
    head_dim: int,
    initializing: bool,
    packed_qkv: bool = False,
    kv_heads: Optional[int] = None,
    mask: Optional[str] = None,
) -> str:
    """The attention core's lowering for one call, chosen from what the
    call can see: ``x [B, T, D]`` is the attention module's input.

    An explicit ``asked`` is taken as given. ``"auto"`` takes a Pallas
    kernel where a custom call is safe (:func:`kernel_is_safe`) and
    pays, by shape: the packed small-T kernel (``"fused"``) where a
    fused QKV projection feeds the core (``packed_qkv``, the caller's
    statement) and it takes the sequence (``flash_packed.supports``:
    T <= 512, the ViT regime); the streaming flash kernels
    (``"pallas"``) where they are ahead (``flash.supports``: T >= 640 on
    the v5e's measurement, head blocks that tile the lanes); else the
    XLA einsum. Under ``mask="block_diffusion"`` a row is ``[noised ‖
    clean]`` and a pass of the kernels walks one half of it: the length
    judged is T // 2.

    What was chosen is counted at trace time: ``attn.impl.<path>``
    (labels ``asked``, ``shape``, ``heads``, and ``kv_heads``, ``mask``
    where the caller names them), and ``attn.mask.<mask>`` (label
    ``impl``) for a caller that names its mask (``"causal"``,
    ``"block_diffusion"``, or ``"window"``, a causal layer that hands
    :func:`dot_product_attention` a window: judged as the causal mask
    is)."""
    impl = asked
    if impl == "auto":
        from distributeddeeplearning_tpu.ops.pallas import flash, flash_packed

        impl = "xla"
        if kernel_is_safe(x, initializing):
            seq_len = x.shape[1] // 2 if mask == "block_diffusion" else x.shape[1]
            if packed_qkv and flash_packed.supports(seq_len, heads, head_dim):
                impl = "fused"
            elif flash.supports(seq_len, heads, head_dim):
                impl = "pallas"
    labels = dict(asked=asked, shape=list(x.shape), heads=heads)
    if kv_heads is not None:
        labels["kv_heads"] = kv_heads
    if mask is not None:
        labels["mask"] = mask
    obs.counter(f"attn.impl.{impl}", **labels)
    if mask is not None:
        obs.counter(f"attn.mask.{mask}", impl=impl)
    return impl


def _xla_attention(q, k, v, *, mask, scale: float):
    """Masked-softmax einsum over BTHD operands with grouped query
    heads; ``mask`` is ``[Tq, Tk]`` bool, True where a query sees a key,
    or None. The ``[Tq, Tk]`` scores and weights go through HBM."""
    b, tq, h, d = q.shape
    kv = k.shape[2]
    scores = jnp.einsum(
        "bqgrd,bkgd->bgrqk", q.reshape(b, tq, kv, h // kv, d), k
    ) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", weights, v).reshape(b, tq, h, d)


def dot_product_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    window: int = 0,
    scale: Optional[float] = None,
    impl: str = "xla",
    axis_name: Optional[str] = None,
) -> jnp.ndarray:
    """Multi-head attention over BTHD tensors, full or causal: ``q [B,
    Tq, H, d]`` against ``k``, ``v`` ``[B, Tk, KV, d]``, ``H // KV``
    query heads to a key head (``xla`` and ``pallas``). ``window``
    (with ``causal``; ``xla`` and ``pallas``): a query sees its own key
    and the ``window − 1`` before it; 0, or a window that covers the
    sequence, is the causal rule.

    ``impl='ring'`` requires running inside ``shard_map`` with the
    sequence dimension sharded over ``axis_name`` (default: the mesh
    convention's ``"seq"`` axis, ``parallel/mesh.py``).
    """
    if window and (not causal or impl not in ("xla", "pallas")):
        raise ValueError(f"a window needs causal=True and impl xla or pallas, got {impl!r}")
    if window >= k.shape[1]:  # the band is the whole triangle: the causal rule
        window = 0
    if impl == "xla":
        mask = None
        if causal:
            tq, tk = q.shape[1], k.shape[1]
            mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
            if window:
                mask &= ~jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq - window)
        scale = scale if scale is not None else q.shape[-1] ** -0.5
        return _xla_attention(q, k, v, mask=mask, scale=scale)
    if impl == "pallas":
        from distributeddeeplearning_tpu.ops.pallas import flash

        if k.shape[2] == q.shape[2] and not window:
            return flash.flash_attention(q, k, v, causal=causal, scale=scale)
        return flash.flash_attention_stats(
            q, k, v, mask=flash.Mask(causal, window=window), scale=scale
        )[0]
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
        mask = block_diffusion_mask(q.shape[1] // 2, block_len)
        return _xla_attention(q, k, v, mask=mask, scale=scale)
    if impl == "pallas":
        return _flash_block_diffusion(q, k, v, block_len, scale)
    raise ValueError(f"unknown block-diffusion attention impl {impl!r}")
