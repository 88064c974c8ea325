"""Decoder-only Transformer LM — the long-context workload tier.

Not in the reference (vision-only; SURVEY.md §5 notes it "scales only
the batch axis"). This framework treats long sequences as first-class:
the LM's causal attention routes through ``ops.dot_product_attention``,
so the same module runs the XLA einsum path, the Pallas flash kernel
(O(T·d) memory — the only way long contexts fit, see
``ops/pallas/flash.py``), or — inside a ``seq``-axis ``shard_map`` —
ring sequence parallelism (``parallel/ring_attention.py``). Which one
is the call's own choice by default (``attn_impl="auto"``,
the rule is ``ops/attention.resolve_impl``): on a TPU with local operands
the flash kernel from 640 tokens on (the packed kernel up to 512), the
einsum elsewhere; no environment variable is needed to get the kernel.

Design mirrors ``models/vit.py``: pre-norm blocks, bf16 compute / f32
params, LayerNorm in f32, every weight annotated with logical axes
(``LOGICAL_RULES`` there apply: heads/mlp → ``model`` for Megatron-style
TP under the pjit engine).

Input ``[B, T]`` int32 tokens → logits ``[B, T, vocab]`` in the compute
dtype (f32 loss math lives in the engine's CE/metrics); pair with
shifted labels and the engine's generalized ``cross_entropy_loss``
(per-token CE). ``data.SyntheticTokenDataset`` supplies the seeded
synthetic stream (the ``FAKE=True`` contract, token edition).
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu.models.vit import ATTN_CORE, Attention, MlpBlock
from distributeddeeplearning_tpu.obs.programs import part

# name -> (hidden, depth, heads, mlp_dim)
_VARIANTS = {
    "tiny": (128, 2, 4, 512),
    "small": (512, 8, 8, 2048),
    "base": (768, 12, 12, 3072),
    "large": (1536, 24, 16, 6144),
}


# Scopes for what no module holds (a module's operations carry its name
# already): the block's residual adds, the embedding lookups, the tied
# output projection.
RESIDUAL = "residual"
EMBED = "embed"
HEAD = "head"

# The parts of this model's train step that a performance change treats
# apart, for `obs/programs.device_seconds_by_scope`: in matching order,
# so `attn_core` (scores, softmax, weighted sum: what a flash kernel
# replaces) stands before the attention module that holds it. The model's
# parts go by the names above and by its modules' names; `loss`,
# `metrics`, `optimizer` and the gradient reduction's `overlap_allreduce`
# (training/overlap.OVERLAP_SCOPE) are the step's own scopes
# (training/train_step.py). An operation in none of them reads as
# unscoped: a new part of the model gets a name, not a wider pattern.
# `ragged-dot-*`: XLA:TPU lowers `lax.ragged_dot` to Mosaic calls of its
# own and names them anew, path and all (`ragged-dot-none`, and
# `ragged-dot-metadata` for the groups' tile tables); a step's only
# ragged products are an expert FFN's (ops/moe.py), so they count there.
RAGGED_DOT = ("ragged-dot-none", "ragged-dot-metadata")
TRAIN_STEP_GROUPS = (
    ("attn_core", part(ATTN_CORE)),
    ("attn_proj", part("attn")),
    ("mlp", part("mlp", *RAGGED_DOT)),
    ("head_loss", part(HEAD, "loss", "metrics")),
    ("optimizer", part("optimizer", "overlap_allreduce")),
    ("norm_residual", part(r"ln\w*", EMBED, RESIDUAL)),
)


class DecoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    dropout: float = 0.0
    seq_axis: Any = None
    decode: bool = False  # KV-cache inference (inference.generate)
    # Paged KV cache (serving tier; see models/vit.Attention): 0 = dense.
    paged_blocks: int = 0
    paged_block_size: int = 0
    # KV-cache storage dtype ("" = compute dtype, "int8"/"fp8" =
    # quantized cache + f32 scales; models/vit.Attention, SERVE_KV_DTYPE).
    kv_dtype: str = ""
    # Decode attention lowering ("xla" | "fused"; models/vit.Attention,
    # SERVE_DECODE_KERNEL).
    decode_kernel: str = "xla"

    @nn.compact
    def __call__(self, x, train: bool = True):
        y = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x).astype(self.dtype)
        a = Attention(
            self.num_heads,
            self.dtype,
            self.attn_impl,
            self.dropout,
            causal=True,
            seq_axis=self.seq_axis,
            decode=self.decode,
            paged_blocks=self.paged_blocks,
            paged_block_size=self.paged_block_size,
            kv_dtype=self.kv_dtype,
            decode_kernel=self.decode_kernel,
            name="attn",
        )(y, train)
        # `residual`: the block's own adds belong to neither module
        with jax.named_scope(RESIDUAL):
            x = x + a
        y = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x).astype(self.dtype)
        m = MlpBlock(self.mlp_dim, self.dtype, self.dropout, name="mlp")(y, train)
        with jax.named_scope(RESIDUAL):
            x = x + m
        return x


class TransformerLM(nn.Module):
    """Causal LM over int32 token ids; returns ``[B, T, vocab]`` logits
    in the compute ``dtype`` (f32 accumulation inside the projection;
    the loss/metric reductions upcast to f32 — ``train_step.py``).

    ``seq_axis``: set to the mesh's sequence axis name (``"seq"``) when
    the model runs *inside* a sequence-parallel ``shard_map``
    (``training/sp_step.py``): positions are then offset by this shard's
    global start, and ``attn_impl="ring"`` attends across shards.
    """

    variant: str = "tiny"
    # Layers to build; 0 = the variant's own depth. A run that must fit a
    # time or memory budget cuts depth and keeps every width
    # (chip_smoke.py serves lm_large's widths at a few layers).
    depth: int = 0
    vocab_size: int = 32_000
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    # "auto": the flash kernel for a long sequence on a TPU, the packed
    # kernel for a short one, the XLA einsum elsewhere (ops/attention.
    # resolve_impl); any other value forces a path.
    attn_impl: str = "auto"
    dropout: float = 0.0
    seq_axis: Any = None
    # Mixture-of-Experts (expert-parallel tier, models/moe.py): 0 = dense.
    # With N experts, every ``moe_every``-th block's FFN is an MoE layer
    # (interleaved, GShard-style); experts shard over the mesh's
    # ``expert`` axis under the GSPMD engine.
    moe_experts: int = 0
    moe_every: int = 2
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # Autoregressive KV-cache inference mode (inference.generate): init
    # with a full-length dummy to size the caches, then feed incremental
    # tokens with mutable=["cache"].
    decode: bool = False
    # Paged KV cache (serving.SlotEngine kv_layout="paged"): the decode
    # caches become one [paged_blocks, paged_block_size, H, Dh] pool per
    # layer addressed through per-row block tables (models/vit.Attention
    # ``_paged_decode_attention``). 0 = dense per-row cache.
    paged_blocks: int = 0
    paged_block_size: int = 0
    # Quantized KV cache (serving.SlotEngine kv_dtype="int8" /
    # SERVE_KV_DTYPE): decode caches store symmetric int8 K/V + one f32
    # scale per head per position; the gather dequantizes before the
    # masked-score math (ops/quant.py). "" = store the compute dtype.
    kv_dtype: str = ""
    # Decode attention lowering (SERVE_DECODE_KERNEL): "xla" = stitched
    # gather→dequant→masked-softmax ops; "fused" = the Pallas
    # online-softmax kernel (ops/pallas/paged_decode.py) on the
    # vector-position decode paths (models/vit.Attention).
    decode_kernel: str = "xla"
    # Gradient checkpointing (rematerialization): recompute each block's
    # activations during backward instead of storing them — trades ~1
    # extra forward of FLOPs for O(depth) activation memory. REMAT=1.
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        if self.variant not in _VARIANTS:
            raise ValueError(f"variant must be one of {sorted(_VARIANTS)}")
        hidden, depth, heads, mlp_dim = _VARIANTS[self.variant]
        depth = self.depth or depth
        b, t = tokens.shape
        if t > self.max_seq_len:
            raise ValueError(f"sequence {t} exceeds max_seq_len {self.max_seq_len}")

        embed = self.param(
            "tok_embed",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")
            ),
            (self.vocab_size, hidden),
            jnp.float32,
        )
        # `embed`: token and position lookups are no module of their own,
        # so they get the scope a module would give them (forward gather,
        # backward scatter-add into the table).
        with jax.named_scope(EMBED):
            x = embed[tokens].astype(self.dtype)
            pos = self.param(
                "pos_embed",
                nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), (None, "seq", "embed")
                ),
                (1, self.max_seq_len, hidden),
                jnp.float32,
            )
            if self.seq_axis is not None and not self.is_initializing():
                # Sequence-parallel: this shard holds global tokens
                # [axis_index*t, (axis_index+1)*t). (Init traces outside
                # shard_map where the axis is unbound; shapes don't depend
                # on the slice, so init uses the prefix.)
                from jax import lax

                start = lax.axis_index(self.seq_axis) * t
                pos_t = lax.dynamic_slice_in_dim(pos[0], start, t, axis=0)[None]
            elif self.decode:
                # Incremental decoding: these t tokens sit at absolute
                # positions [pos_index, pos_index+t). The counter lives in
                # the cache collection beside the attention KV caches. Like
                # the attention cache_index it may be a scalar (lockstep
                # batch, inference.generate) or a [B] vector of per-row
                # positions (serving.SlotEngine) — the vector path gathers
                # each row's positions independently.
                from jax import lax

                pidx = self.variable(
                    "cache", "pos_index", lambda: jnp.zeros((), jnp.int32)
                )
                if self.is_initializing():
                    pos_t = pos[:, :t]
                else:
                    start = pidx.value
                    if jnp.ndim(start) == 0:
                        pos_t = lax.dynamic_slice_in_dim(
                            pos[0], start, t, axis=0
                        )[None]
                    else:
                        # [B, t, hidden]: row b reads pos[start[b] .. +t)
                        pos_t = jnp.take(
                            pos[0], start[:, None] + jnp.arange(t), axis=0
                        )
                    pidx.value = start + t
            else:
                pos_t = pos[:, :t]
            x = x + pos_t.astype(self.dtype)
        if self.dropout > 0:
            x = nn.Dropout(self.dropout, deterministic=not train)(x)

        dense_block, moe_block = DecoderBlock, None
        if self.moe_experts:
            from distributeddeeplearning_tpu.models.moe import MoEDecoderBlock

            moe_block = MoEDecoderBlock
        if self.remat and not self.decode:
            # static_argnums: `train` is a Python bool, not a tracer
            dense_block = nn.remat(DecoderBlock, static_argnums=(2,))
            if moe_block is not None:
                moe_block = nn.remat(moe_block, static_argnums=(2,))
        for i in range(depth):
            if self.moe_experts and i % self.moe_every == self.moe_every - 1:
                # Decode runs the mixture WITHOUT capacity dropping:
                # dropping is a training-efficiency trick whose outcome
                # depends on the chunk length, so it can never be
                # consistent between incremental and full-sequence
                # evaluation. capacity_factor = num_experts ⇒ capacity =
                # k·s — every token always fits.
                x = moe_block(
                    heads,
                    mlp_dim,
                    self.moe_experts,
                    self.moe_top_k,
                    float(self.moe_experts)
                    if self.decode
                    else self.moe_capacity_factor,
                    dtype=self.dtype,
                    attn_impl=self.attn_impl,
                    dropout=self.dropout,
                    seq_axis=self.seq_axis,
                    decode=self.decode,
                    paged_blocks=self.paged_blocks,
                    paged_block_size=self.paged_block_size,
                    kv_dtype=self.kv_dtype,
                    decode_kernel=self.decode_kernel,
                    name=f"block{i}",
                )(x, train)
            else:
                x = dense_block(
                    heads,
                    mlp_dim,
                    self.dtype,
                    self.attn_impl,
                    self.dropout,
                    seq_axis=self.seq_axis,
                    decode=self.decode,
                    paged_blocks=self.paged_blocks,
                    paged_block_size=self.paged_block_size,
                    kv_dtype=self.kv_dtype,
                    decode_kernel=self.decode_kernel,
                    name=f"block{i}",
                )(x, train)

        x = nn.LayerNorm(dtype=jnp.float32, name="ln_final")(x)
        # Tied output projection (standard LM practice; halves embedding
        # params vs an untied head). Operands in the compute dtype so the
        # MXU runs at full bf16 rate with f32 accumulation; the [B, T, V]
        # logits tensor is then STORED in the compute dtype (at vocab-32k
        # it is the model's largest activation, and its cotangent — the
        # projection backward's operand — stays bf16 too). The loss reads
        # them as they are, upcasting inside its reductions, and keeps
        # them as its residual: no f32 copy exists
        # (train_step._sparse_softmax_ce).
        # `head`: the tied projection is no module of its own, so it gets
        # the scope a module would give it.
        with jax.named_scope(HEAD):
            logits = jnp.einsum(
                "btd,vd->btv",
                x.astype(self.dtype),
                embed.astype(self.dtype),
                preferred_element_type=jnp.float32,
            )
            return logits.astype(self.dtype)


LM_Tiny = functools.partial(TransformerLM, variant="tiny")
LM_Small = functools.partial(TransformerLM, variant="small")
LM_Base = functools.partial(TransformerLM, variant="base")
LM_Large = functools.partial(TransformerLM, variant="large")
