"""Vision Transformer (ViT) family with tensor-parallel sharding annotations.

Not in the reference (vision-conv only); required by BASELINE.json's
configs ("ViT-B/16 on ImageNet — non-conv allreduce workload, v5e-64").
Design is TPU-first throughout:

* Every weight is annotated with **logical axes** via
  ``nn.with_logical_partitioning``; the model-neutral rules table
  (``models/sharding.py``) maps them onto mesh axes so the same module
  runs pure-DP (rules map model dims to None) or tensor-parallel
  (attention heads + MLP hidden sharded over ``model``) without touching
  the module. The pjit engine (``training/pjit_step.py``) consumes
  these annotations.
* Attention goes through ``ops.dot_product_attention`` so the impl can
  be swapped (XLA einsum / Pallas flash kernel / ring sequence-parallel)
  per config; the default ``"auto"`` picks a kernel from shape and
  platform (the rule lives in ``ops/attention.resolve_impl``).
* bf16 compute, f32 params; LayerNorm in f32 (TPU numerics practice).

Variant table follows the standard ViT paper sizes; patch size via name
suffix (``vit_b16`` = Base, 16x16 patches).
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu.ops.attention import (
    dot_product_attention,
    resolve_impl,
)

# Sub-scope of an Attention module for scores, softmax and weighted sum
# (whichever lowering runs them): what a flash kernel replaces, apart
# from the q/k/v and output projections (obs/programs.py groups by it).
ATTN_CORE = "attn_core"

# name -> (hidden, depth, heads, mlp_dim)
_VARIANTS = {
    "ti": (192, 12, 3, 768),
    "s": (384, 12, 6, 1536),
    "b": (768, 12, 12, 3072),
    "l": (1024, 24, 16, 4096),
    "h": (1280, 32, 16, 5120),
}

# Model-neutral rules table (models/sharding.py), re-exported here for
# backward compatibility — importing from models.sharding is preferred.
from distributeddeeplearning_tpu.models.sharding import (  # noqa: F401
    DATA_PARALLEL_RULES,
    LOGICAL_RULES,
)


def _dense(features, name, kernel_axes, dtype, use_bias=True):
    return nn.Dense(
        features,
        dtype=dtype,
        param_dtype=jnp.float32,
        use_bias=use_bias,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.xavier_uniform(), kernel_axes
        ),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros, (kernel_axes[-1],)
        ),
        name=name,
    )


class MlpBlock(nn.Module):
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    dropout: float = 0.0

    @nn.compact
    def __call__(self, x, train: bool = True):
        d = x.shape[-1]
        x = _dense(self.mlp_dim, "fc1", ("embed", "mlp"), self.dtype)(x)
        x = nn.gelu(x)
        if self.dropout > 0:
            x = nn.Dropout(self.dropout, deterministic=not train)(x)
        x = _dense(d, "fc2", ("mlp", "embed"), self.dtype)(x)
        if self.dropout > 0:
            x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return x


class Attention(nn.Module):
    num_heads: int
    dtype: Any = jnp.bfloat16
    # "auto" resolves per call (ops/attention.resolve_impl): the packed
    # small-T kernel or the streaming flash kernel where one applies,
    # the XLA einsum otherwise. Explicit values ("xla" | "pallas" |
    # "ring" | "fused") force a path.
    attn_impl: str = "xla"
    dropout: float = 0.0
    causal: bool = False  # decoder-only use (models/transformer_lm.py)
    seq_axis: Any = None  # mesh axis for impl='ring' (default "seq")
    # Autoregressive inference: maintain a KV cache in the "cache"
    # collection. Init with the FULL-length dummy input (that sizes the
    # cache buffers), then apply with the prompt / one token at a time
    # and mutable=["cache"] (driver: ``inference.generate``).
    decode: bool = False
    # Paged KV cache (serving tier): ``paged_blocks > 0`` replaces the
    # dense [B, max_len, H, Dh] cache rows with one shared pool of
    # [paged_blocks, paged_block_size, H, Dh] per layer, addressed
    # through a per-row int32 ``block_table`` cache leaf (logical block
    # = position // block_size). Decode writes scatter through the
    # table; attention gathers by it. Table entry 0 is the trash sink
    # (``serving.blocks``) — padded-tail writes land there, position
    # masks keep it unread. Requires per-row (vector) cache positions.
    paged_blocks: int = 0
    paged_block_size: int = 0
    # KV-cache storage dtype (serving tier, SERVE_KV_DTYPE): "" keeps
    # the compute dtype; "int8" stores symmetric int8 K/V plus one f32
    # scale per head per position (ops/quant.py), "fp8" stores
    # float8_e4m3fn with the same scale contract — writes quantize, the
    # decode path dequantizes to the compute dtype before the masked
    # scores (in-register under decode_kernel="fused"). Halves the
    # per-step KV bytes decode streams (scale overhead 4/Dh per
    # element, itemized by decode_audit). Validated through the
    # ops/quant.py dtype registry so every boundary names the same
    # supported list.
    kv_dtype: str = ""
    # Decode attention lowering (serving tier, SERVE_DECODE_KERNEL):
    # "xla" stitches gather → dequant → masked einsum from stock ops
    # (materializing a full-length compute-dtype K/V view); "fused"
    # runs the Pallas online-softmax kernel (ops/pallas/paged_decode.py)
    # that walks the block table / dense rows and dequantizes
    # in-register — same masked-score math, no full-length HBM
    # round-trip. Applies to the vector-position decode windows of the
    # serving engine (decode step, speculative verify); prefill and
    # scalar-position callers (inference.generate) stay on the XLA path.
    decode_kernel: str = "xla"

    def _kv_quantized(self) -> bool:
        from distributeddeeplearning_tpu.ops import quant as quantlib

        quantlib.validate_store_dtype(
            "kv_dtype", self.kv_dtype, extra=("",)
        )
        return self.kv_dtype not in ("", "bf16")

    def _decode_fused(self, t: int) -> bool:
        """Whether a ``t``-row vector-position window runs the fused
        kernel: decode steps and speculative verify windows do; a
        prefill-sized window (the paged prefill's ``t`` = bucket) is
        MXU work and keeps the einsum, as the dense prefill always has."""
        if self.decode_kernel not in ("xla", "fused"):
            raise ValueError(
                f"decode_kernel must be one of ('xla', 'fused'), got "
                f"{self.decode_kernel!r}"
            )
        from distributeddeeplearning_tpu.ops.pallas.paged_decode import (
            MAX_QUERY_ROWS,
        )

        return self.decode_kernel == "fused" and t <= MAX_QUERY_ROWS

    def _paged_decode_attention(self, q, k, v, ci):
        """Block-table-indexed variant of the decode cache: same math
        per row as the dense path at the same positions, but K/V live in
        the shared block pool. Writes beyond the table's logical range
        are routed to the trash block (clamped gather indices would
        otherwise alias REAL tail blocks)."""
        nb, bs = self.paged_blocks, self.paged_block_size
        b, t = q.shape[0], q.shape[1]
        heads, dh = k.shape[-2], k.shape[-1]
        quant = self._kv_quantized()
        if quant:
            from distributeddeeplearning_tpu.ops import quant as quantlib

            kv_dt = quantlib.kv_store_dtype(self.kv_dtype)
        else:
            kv_dt = k.dtype
        max_blocks = -(-k.shape[1] // bs) if self.is_initializing() else None
        ck = self.variable(
            "cache", "paged_k", jnp.zeros, (nb, bs, heads, dh), kv_dt
        )
        cv = self.variable(
            "cache", "paged_v", jnp.zeros, (nb, bs, heads, dh), kv_dt
        )
        if quant:
            # One f32 scale per head per pool position, resident beside
            # the int8 payload (same block addressing — the trash-block
            # and prefix-sharing invariants cover scales for free).
            cks = self.variable(
                "cache", "paged_k_scale", jnp.zeros,
                (nb, bs, heads, 1), jnp.float32,
            )
            cvs = self.variable(
                "cache", "paged_v_scale", jnp.zeros,
                (nb, bs, heads, 1), jnp.float32,
            )
        bt = self.variable(
            "cache", "block_table",
            lambda: jnp.zeros((b, max_blocks), jnp.int32),
        )
        if self.is_initializing():
            return dot_product_attention(q, k, v, causal=self.causal)
        idx = ci.value
        if jnp.ndim(idx) == 0:
            raise ValueError(
                "paged decode requires per-row (vector) cache positions "
                "— the serving engine's path; inference.generate stays "
                "on the dense cache"
            )
        table = bt.value  # [B, max_blocks]
        mb = table.shape[1]
        pos = idx[:, None] + jnp.arange(t)  # [B, t] absolute positions
        lb = pos // bs
        # Out-of-range logical blocks (a bucket-padded prefill tail) go
        # to the trash block; clamping alone would overwrite real rows.
        pb = jnp.where(
            lb < mb,
            jnp.take_along_axis(table, jnp.clip(lb, 0, mb - 1), axis=1),
            jnp.int32(0),
        )
        flat = (pb * bs + pos % bs).reshape(-1)  # [B*t] pool row ids
        if quant:
            from distributeddeeplearning_tpu.ops.quant import quantize_kv

            # 8-bit payload + [B,t,H,1] f32 scales (int8 or fp8)
            k, k_scale = quantize_kv(k, self.kv_dtype, axis=-1)
            v, v_scale = quantize_kv(v, self.kv_dtype, axis=-1)
            cks.value = (
                cks.value.reshape(nb * bs, heads, 1)
                .at[flat].set(k_scale.reshape(-1, heads, 1))
                .reshape(nb, bs, heads, 1)
            )
            cvs.value = (
                cvs.value.reshape(nb * bs, heads, 1)
                .at[flat].set(v_scale.reshape(-1, heads, 1))
                .reshape(nb, bs, heads, 1)
            )
        ck.value = (
            ck.value.reshape(nb * bs, heads, dh)
            .at[flat].set(k.reshape(-1, heads, dh))
            .reshape(nb, bs, heads, dh)
        )
        cv.value = (
            cv.value.reshape(nb * bs, heads, dh)
            .at[flat].set(v.reshape(-1, heads, dh))
            .reshape(nb, bs, heads, dh)
        )
        ci.value = idx + t
        if self._decode_fused(t):
            # Fused tier: the kernel walks the table itself — physical
            # blocks stream through VMEM in the storage dtype and
            # dequantize in-register; the [B, mb*bs, H, Dh] gathered
            # view below never materializes.
            from distributeddeeplearning_tpu.ops.pallas.paged_decode import (
                fused_decode_attention,
            )

            with jax.named_scope(ATTN_CORE):
                return fused_decode_attention(
                    q, ck.value, cv.value, pos,
                    k_scale=cks.value if quant else None,
                    v_scale=cvs.value if quant else None,
                    block_table=table, block_size=bs,
                )
        # Gather this row's logical view [B, mb*bs, H, Dh]; positions
        # beyond the written depth are masked exactly like the dense
        # path's unwritten tail (bitwise-invariant: masked scores are
        # -inf -> exact zeros in the softmax/weighted sum).
        k_all = jnp.take(ck.value, table, axis=0).reshape(b, mb * bs, heads, dh)
        v_all = jnp.take(cv.value, table, axis=0).reshape(b, mb * bs, heads, dh)
        if quant:
            from distributeddeeplearning_tpu.ops.quant import dequantize_store

            k_all = dequantize_store(
                k_all,
                jnp.take(cks.value, table, axis=0)
                .reshape(b, mb * bs, heads, 1),
                self.dtype,
            )
            v_all = dequantize_store(
                v_all,
                jnp.take(cvs.value, table, axis=0)
                .reshape(b, mb * bs, heads, 1),
                self.dtype,
            )
        return self._masked_decode_scores(q, k_all, v_all, pos)

    def _masked_decode_scores(self, q, k_all, v_all, q_pos):
        """Shared tail of both decode cache layouts: position-masked
        attention of q ([B, t, H, Dh]) over the full static cache view."""
        length = k_all.shape[1]
        head_dim = q.shape[-1]
        with jax.named_scope(ATTN_CORE):
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", (q * head_dim**-0.5), k_all
            ).astype(jnp.float32)
            k_pos = jnp.arange(length)
            if q_pos.ndim == 1:
                mask = (k_pos[None, :] <= q_pos[:, None])[None, None]  # [1,1,t,L]
            else:
                mask = (k_pos[None, None, :] <= q_pos[:, :, None])[:, None]
            scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, v_all)

    def _decode_attention(self, q, k, v):
        """Single/few-token query against the growing KV cache. Static
        shapes throughout: the cache is full-length from init and a
        position mask hides the not-yet-written tail.

        ``cache_index`` may be a scalar (``inference.generate``: the
        whole batch decodes in lockstep) or a ``[B]`` vector of per-row
        positions (``serving.SlotEngine``: each batch row is an
        independent request slot at its own depth). The vector path
        writes K/V per row and masks per row; the math per row is
        identical to the scalar path at that row's position.

        Multi-token windows (``t > 1``) compose with the vector path —
        the decode-verify view of the speculative tier: row ``b``'s
        ``t`` K/V rows land at ``idx[b] .. idx[b]+t-1`` BEFORE the
        gather, and the ``[B, t]`` position grid masks each query to
        its own prefix, so candidate ``j`` attends the committed
        context plus candidates ``< j`` exactly. Contract: callers keep
        ``idx[b] + t <= max_len`` — ``dynamic_update_slice`` clamps an
        out-of-range start backwards, which would silently overwrite
        committed rows (the serving engine reserves ``spec_k`` headroom
        at admission)."""
        from jax import lax

        ci = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        if self.paged_blocks:
            return self._paged_decode_attention(q, k, v, ci)
        quant = self._kv_quantized()
        if quant:
            from distributeddeeplearning_tpu.ops import quant as quantlib

            kv_dt = quantlib.kv_store_dtype(self.kv_dtype)
        else:
            kv_dt = k.dtype
        ck = self.variable("cache", "cached_k", jnp.zeros, k.shape, kv_dt)
        cv = self.variable("cache", "cached_v", jnp.zeros, v.shape, kv_dt)
        if quant:
            # f32 scale per head per position (size-1 tail axis so the
            # K-shaped write indices apply verbatim).
            cks = self.variable(
                "cache", "cached_k_scale", jnp.zeros,
                k.shape[:-1] + (1,), jnp.float32,
            )
            cvs = self.variable(
                "cache", "cached_v_scale", jnp.zeros,
                v.shape[:-1] + (1,), jnp.float32,
            )
        if self.is_initializing():
            # init traces the full-length dummy: buffers get their final
            # [B, max_len, H, Dh] shape; run the normal path for tracing.
            return dot_product_attention(q, k, v, causal=self.causal)
        t = q.shape[1]
        idx = ci.value
        writes = [(ck, k), (cv, v)]
        if quant:
            from distributeddeeplearning_tpu.ops.quant import (
                dequantize_store,
                quantize_kv,
            )

            kq, k_scale = quantize_kv(k, self.kv_dtype, axis=-1)
            vq, v_scale = quantize_kv(v, self.kv_dtype, axis=-1)
            writes = [(ck, kq), (cv, vq), (cks, k_scale), (cvs, v_scale)]
        if jnp.ndim(idx) == 0:
            for var, upd in writes:
                var.value = lax.dynamic_update_slice(
                    var.value, upd, (0, idx, 0, 0)
                )
            # query i sits at absolute position idx+i; it may attend to
            # all cache slots <= that position (causal + written-so-far
            # in one)
            q_pos = idx + jnp.arange(t)  # [t]
        else:
            # Per-row positions: write row b's K/V at idx[b] (a vmapped
            # dynamic_update_slice lowers to a per-row scatter).
            write = jax.vmap(
                lambda c, u, i: lax.dynamic_update_slice(c, u, (i, 0, 0))
            )
            for var, upd in writes:
                var.value = write(var.value, upd, idx)
            q_pos = idx[:, None] + jnp.arange(t)  # [B, t]
        ci.value = idx + t
        if q_pos.ndim == 2 and self._decode_fused(t):
            # Fused tier, dense rows: storage-dtype cache streams
            # through the kernel block-wise, dequant in-register — the
            # full-length dequantized copy below never materializes.
            # Scalar-position callers (inference.generate's lockstep
            # batch, the dense prefill program) keep the XLA path: the
            # fused kernel's contract is per-row positions.
            from distributeddeeplearning_tpu.ops.pallas.paged_decode import (
                fused_decode_attention,
            )

            with jax.named_scope(ATTN_CORE):
                return fused_decode_attention(
                    q, ck.value, cv.value, q_pos,
                    k_scale=cks.value if quant else None,
                    v_scale=cvs.value if quant else None,
                )
        if quant:
            k_all = dequantize_store(ck.value, cks.value, self.dtype)
            v_all = dequantize_store(cv.value, cvs.value, self.dtype)
        else:
            k_all, v_all = ck.value, cv.value
        return self._masked_decode_scores(q, k_all, v_all, q_pos)

    @nn.compact
    def __call__(self, x, train: bool = True):
        d = x.shape[-1]
        head_dim = d // self.num_heads
        qkv_flat = _dense(3 * d, "qkv", ("embed", "heads"), self.dtype)(x)
        # Params don't depend on the impl, and ring needs a bound mesh
        # axis — init (traced outside shard_map) uses the xla path.
        # The core's lowering for this call (ops/attention.resolve_impl);
        # ``decode=True`` never asks.
        impl = None if self.decode else resolve_impl(
            self.attn_impl, x, heads=self.num_heads, head_dim=head_dim,
            initializing=self.is_initializing(), packed_qkv=True,
        )
        if impl == "ring" and self.is_initializing():
            impl = "xla"
        packed = None
        if impl == "fused":
            from distributeddeeplearning_tpu.ops.pallas.flash_packed import (
                fused_qkv_attention as packed,
            )
        elif impl == "pallas":
            from distributeddeeplearning_tpu.ops.pallas import flash

            if flash.heads_per_program(self.num_heads, head_dim):
                packed = flash.flash_qkv_attention
        if packed is not None:
            # Packed path: no [B, T, 3, H, d] reshape/slice at the XLA
            # level — the kernels read head columns from qkv directly.
            with jax.named_scope(ATTN_CORE):
                out_flat = packed(qkv_flat, self.num_heads, causal=self.causal)
        else:
            qkv = qkv_flat.reshape(*x.shape[:-1], 3, self.num_heads, head_dim)
            q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
            if self.decode:
                if not self.causal:
                    raise ValueError("decode=True requires causal attention")
                out = self._decode_attention(q, k, v)
            else:
                with jax.named_scope(ATTN_CORE):
                    out = dot_product_attention(
                        q,
                        k,
                        v,
                        causal=self.causal,
                        impl=impl,
                        axis_name=self.seq_axis,
                    )
            out_flat = out.reshape(*x.shape[:-1], d)
        out = _dense(d, "proj", ("heads", "embed"), self.dtype)(out_flat)
        if self.dropout > 0:
            out = nn.Dropout(self.dropout, deterministic=not train)(out)
        return out


class EncoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    attn_impl: str = "xla"
    dropout: float = 0.0

    @nn.compact
    def __call__(self, x, train: bool = True):
        # Pre-norm; LayerNorm in f32 for stable statistics under bf16.
        y = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x).astype(self.dtype)
        x = x + Attention(
            self.num_heads, self.dtype, self.attn_impl, self.dropout, name="attn"
        )(y, train)
        y = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x).astype(self.dtype)
        x = x + MlpBlock(self.mlp_dim, self.dtype, self.dropout, name="mlp")(y, train)
        return x


class ViT(nn.Module):
    """ViT with a classification head (cls-token pooling)."""

    variant: str = "b"
    patch_size: int = 16
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16
    # "auto": packed small-T Pallas attention on TPU (T=197 is its
    # regime — PROFILE.md round-4), XLA einsum elsewhere/otherwise.
    attn_impl: str = "auto"
    dropout: float = 0.0
    # Gradient checkpointing: recompute block activations in backward
    # (REMAT=1 via config) — O(depth) activation memory for one extra fwd.
    remat: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        if self.variant not in _VARIANTS:
            raise ValueError(f"variant must be one of {sorted(_VARIANTS)}")
        hidden, depth, heads, mlp_dim = _VARIANTS[self.variant]
        b, h, w, _ = x.shape
        if h % self.patch_size or w % self.patch_size:
            raise ValueError(
                f"image size {h}x{w} not divisible by patch {self.patch_size}"
            )
        x = jnp.asarray(x, self.dtype)
        x = nn.Conv(
            hidden,
            (self.patch_size, self.patch_size),
            strides=(self.patch_size, self.patch_size),
            padding="VALID",
            dtype=self.dtype,
            param_dtype=jnp.float32,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), (None, None, None, "embed")
            ),
            name="patch_embed",
        )(x)
        x = x.reshape(b, -1, hidden)
        n_tokens = x.shape[1]

        cls = self.param(
            "cls_token",
            nn.with_logical_partitioning(nn.initializers.zeros, (None, None, "embed")),
            (1, 1, hidden),
            jnp.float32,
        )
        x = jnp.concatenate([jnp.tile(cls.astype(self.dtype), (b, 1, 1)), x], axis=1)
        pos = self.param(
            "pos_embed",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), (None, "seq", "embed")
            ),
            (1, n_tokens + 1, hidden),
            jnp.float32,
        )
        x = x + pos.astype(self.dtype)

        block = (
            nn.remat(EncoderBlock, static_argnums=(2,))
            if self.remat
            else EncoderBlock
        )
        for i in range(depth):
            x = block(
                heads,
                mlp_dim,
                self.dtype,
                self.attn_impl,
                self.dropout,
                name=f"block{i}",
            )(x, train)

        x = nn.LayerNorm(dtype=jnp.float32, name="ln_final")(x)
        x = x[:, 0]  # cls token
        x = _dense(self.num_classes, "head", ("embed", "classes"), jnp.float32)(x)
        return jnp.asarray(x, jnp.float32)


ViT_B16 = functools.partial(ViT, variant="b", patch_size=16)
ViT_S16 = functools.partial(ViT, variant="s", patch_size=16)
ViT_Ti16 = functools.partial(ViT, variant="ti", patch_size=16)
ViT_L16 = functools.partial(ViT, variant="l", patch_size=16)
