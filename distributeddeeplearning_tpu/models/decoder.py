"""A decoder built from a layer spec.

``models/transformer_lm.TransformerLM`` is one block (learned
positions, LayerNorm, a fused ``3·d`` projection over equal heads, GELU
MLP, tied head) in four sizes. The decoders people deploy differ from it
in every one of those places, so this module builds the block from a
:class:`DecoderSpec`: RMSNorm or LayerNorm with a settable epsilon,
rotary or learned positions, grouped-query heads whose width is not
``hidden / heads``, a per-head q/k norm, biases or none, a GELU MLP or
routed gated experts (SiLU or ReLU gate) of which this process holds a
share (``ops/moe.py``), a router that reads the FFN's normed input or
attention's, a tied or an untied head. The layers need not be alike: a
``pattern`` of :class:`LayerKind` repeats over the depth and gives each
layer its attention window and whether it has rotary positions.

**Three masks**: the causal one (a layer whose window is 0), the causal
one within a window of the last keys (``LayerKind.window``), and the
block-diffusion one (``spec.block_len``, every layer, no window). Their
cores are ``ops/attention``'s (``dot_product_attention``, ``block_
diffusion_attention``), and which lowering a call takes is that
module's rule (``resolve_impl``): this model states its heads and its
mask, and that no fused QKV projection feeds the core.

Scope names are the ones ``transformer_lm.TRAIN_STEP_GROUPS`` reads:
module ``attn`` with ``attn_core`` inside it, the FFN module ``mlp``,
norms ``ln*``, scopes ``embed``, ``residual``, ``head``. Inside
``attn_core`` a causal layer's core runs under ``attn_window`` or
``attn_full`` (:data:`ATTN_KIND_GROUPS`). Inside ``mlp`` the expert
layer has four scopes of its own, which :data:`MOE_GROUPS` reads:
``moe_route``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``.

**Block diffusion** (``spec.block_len`` > 0; BD3-LM, arXiv:2503.09573,
as SDAR, arXiv:2510.06303, trains with it): the model reads a row as
``[noised ‖ clean]``, ``2L`` tokens with positions ``[0..L−1, 0..L−1]``,
under the mask of ``ops/attention.block_diffusion_mask``, and its head
reads the noised half alone: ``[B, 2L] -> [B, L, vocab]``. The noising
is the input path's (``data/noise.py``), the weighted loss the step's.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.models.transformer_lm import (
    EMBED,
    HEAD,
    RAGGED_DOT,
    RESIDUAL,
)
from distributeddeeplearning_tpu.models.vit import ATTN_CORE, MlpBlock
from distributeddeeplearning_tpu.obs.programs import part
from distributeddeeplearning_tpu.ops import moe as moe_ops
from distributeddeeplearning_tpu.ops.attention import (
    block_diffusion_attention,
    dot_product_attention,
    resolve_impl,
)

# Collection the expert layers sow their counts into; the train step
# reports each name's mean over the layers beside its own metrics.
STATS = "stats"

# The expert layer's parts of a train step, for `obs/programs.
# device_seconds_by_scope` (all of them lie inside `mlp` of
# `transformer_lm.TRAIN_STEP_GROUPS`): dispatch and combine are one
# group, the data movement round the products; the products' kernels go
# by the names XLA gives them (`transformer_lm.RAGGED_DOT`).
MOE_GROUPS = (
    ("moe_route", part(moe_ops.ROUTE)),
    ("moe_dispatch", part(moe_ops.DISPATCH, moe_ops.COMBINE)),
    ("moe_experts", part(moe_ops.EXPERTS, *RAGGED_DOT)),
)


# The attention core of a causal layer by its kind, inside `attn_core`:
# what `LayerKind.window` decides.
ATTN_WINDOW, ATTN_FULL = "attn_window", "attn_full"
ATTN_KIND_GROUPS = (
    (ATTN_WINDOW, part(ATTN_WINDOW)),
    (ATTN_FULL, part(ATTN_FULL)),
)


class LayerKind(NamedTuple):
    """What the layers of one model differ in. ``window``: a query sees
    its own key and the ``window − 1`` before it (0: every key up to its
    own). ``rope``: q and k get the spec's rotary positions (False: no
    positions at all in this layer)."""

    window: int = 0
    rope: bool = True


@dataclasses.dataclass(frozen=True)
class DecoderSpec:
    """One decoder layer, how many of it, and what differs among them."""

    hidden: int
    layers: int
    heads: int  # query heads
    kv_heads: int
    head_dim: int
    norm: str = "rms"  # "rms" | "layer"
    norm_eps: float = 1e-6
    positions: str = "rope"  # "rope" | "learned"
    rope_theta: float = 1e6
    qk_norm: bool = False  # per-head norm of q and k before the positions
    bias: bool = False
    ffn: str = "moe"  # "moe": routed gated experts | "gelu": GELU MLP
    ffn_dim: int = 0  # an expert's width, or the MLP's
    experts: int = 0  # the router's width
    experts_held: int = 0  # how many of them this process holds ...
    first_expert: int = 0  # ... from which on
    experts_per_token: int = 0
    tied_head: bool = False
    block_len: int = 0  # block-diffusion mask over [noised ‖ clean]; 0 = causal
    # layer l is pattern[l % len(pattern)]; (): every layer LayerKind()
    pattern: Tuple[LayerKind, ...] = ()
    route_before_attention: bool = False  # the router reads ln1's output
    activation: str = "silu"  # the experts' gate: "silu" | "relu" (ReGLU)

    def kind(self, layer: int) -> LayerKind:
        return self.pattern[layer % len(self.pattern)] if self.pattern else LayerKind()


# name -> spec. `sdar_30b_a3b`: SDAR-30B-A3B-Chat's published layer
# (huggingface.co/JetLM/SDAR-30B-A3B-Chat config.json, `sdar_moe`): all
# 48 layers and 128 experts, which no one chip holds; a run states its
# share (`layers`, `experts_held`, `first_expert`) through `get_model`.
# The q/k norm is the Qwen3-MoE modelling code's, block length 4 the one
# SDAR's chat models generate with (the config gives neither).
SPECS: Dict[str, DecoderSpec] = {
    "sdar_30b_a3b": DecoderSpec(
        hidden=2048, layers=48, heads=32, kv_heads=4, head_dim=128,
        norm="rms", norm_eps=1e-6, positions="rope", rope_theta=1e6,
        qk_norm=True, bias=False, ffn="moe", ffn_dim=768, experts=128,
        experts_held=128, experts_per_token=8, tied_head=False, block_len=4,
    ),
    # the same block at a size for tests and smoke runs
    "sdar_tiny": DecoderSpec(
        hidden=64, layers=2, heads=4, kv_heads=2, head_dim=16,
        qk_norm=True, ffn="moe", ffn_dim=32, experts=8, experts_held=8,
        experts_per_token=2, block_len=4,
    ),
    # SmallThinker-21BA3B-Instruct's published layers (huggingface.co/
    # PowerInfer/SmallThinker-21BA3B-Instruct config.json; the family's
    # report is arXiv:2507.20984): 52 layers in periods of four, a full-
    # attention layer without positions, then three with rotary positions
    # and a window of 4,096 (`sliding_window_layout`, `rope_layout`); the
    # router reads attention's normed input (from the catalog's "router
    # placed before attention"; not checked against the modelling code,
    # PERF.md section 7); ReGLU experts, 6 of 64, no shared expert. A run
    # states its share, as for `sdar_30b_a3b`.
    "smallthinker_21b_a3b": DecoderSpec(
        hidden=2560, layers=52, heads=28, kv_heads=4, head_dim=128,
        norm="rms", norm_eps=1e-6, positions="rope", rope_theta=1.5e6,
        qk_norm=False, bias=False, ffn="moe", ffn_dim=768, experts=64,
        experts_held=64, experts_per_token=6, tied_head=False,
        pattern=(LayerKind(0, False),) + (LayerKind(4096, True),) * 3,
        route_before_attention=True, activation="relu",
    ),
    # the same at a size for tests: two periods, a window of 8
    "smallthinker_tiny": DecoderSpec(
        hidden=64, layers=8, heads=4, kv_heads=2, head_dim=16,
        ffn="moe", ffn_dim=32, experts=8, experts_held=8,
        experts_per_token=2,
        pattern=(LayerKind(0, False),) + (LayerKind(8, True),) * 3,
        route_before_attention=True, activation="relu",
    ),
    # GPT-2's block (models/transformer_lm.py `tiny`), to show the spec
    # reaches it: the q, k, v kernels are the thirds of its fused one
    "gpt2_tiny": DecoderSpec(
        hidden=128, layers=2, heads=4, kv_heads=4, head_dim=32,
        norm="layer", positions="learned", bias=True, ffn="gelu",
        ffn_dim=512, tied_head=True,
    ),
}


class RMSNorm(nn.Module):
    """``x · rsqrt(mean(x²) + eps) · scale`` over the last axis, float32."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps
        ) * scale


def _norm(spec: DecoderSpec, name: str):
    if spec.norm == "layer":  # flax's own, under the name a GPT-2 tree has
        return nn.LayerNorm(epsilon=spec.norm_eps, dtype=jnp.float32, name=name)
    return RMSNorm(spec.norm_eps, name=name)


def rotary(x, positions, theta: float):
    """Rotary positions over ``x [B, T, H, d]`` (halves rotated against
    each other, the ``rotate_half`` convention), in float32."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]  # [T, d/2]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x = x.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _dense(features: int, name: str, spec: DecoderSpec, dtype):
    return nn.Dense(
        features, use_bias=spec.bias, dtype=dtype, param_dtype=jnp.float32,
        kernel_init=nn.initializers.normal(0.02), name=name,
    )


class SpecAttention(nn.Module):
    spec: DecoderSpec
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    kind: LayerKind = LayerKind()

    @nn.compact
    def __call__(self, x, positions):
        spec, kind = self.spec, self.kind
        b, t, _ = x.shape
        h, kv, hd = spec.heads, spec.kv_heads, spec.head_dim
        q = _dense(h * hd, "q", spec, self.dtype)(x).reshape(b, t, h, hd)
        k = _dense(kv * hd, "k", spec, self.dtype)(x).reshape(b, t, kv, hd)
        v = _dense(kv * hd, "v", spec, self.dtype)(x).reshape(b, t, kv, hd)
        if spec.qk_norm:
            q = RMSNorm(spec.norm_eps, name="q_norm")(q)
            k = RMSNorm(spec.norm_eps, name="k_norm")(k)
        if spec.positions == "rope" and kind.rope:
            q = rotary(q, positions, spec.rope_theta)
            k = rotary(k, positions, spec.rope_theta)
        q, k = q.astype(self.dtype), k.astype(self.dtype)
        # separate q/k/v projections: the packed kernel is not on offer
        impl = resolve_impl(
            self.attn_impl, x, heads=h, head_dim=hd,
            initializing=self.is_initializing(), kv_heads=kv,
            mask="block_diffusion" if spec.block_len
            else "window" if kind.window else "causal",
        )
        with jax.named_scope(ATTN_CORE):
            if spec.block_len:
                out = block_diffusion_attention(
                    q, k, v, block_len=spec.block_len, impl=impl
                )
            else:
                with jax.named_scope(ATTN_WINDOW if kind.window else ATTN_FULL):
                    out = dot_product_attention(
                        q, k, v, causal=True, window=kind.window, impl=impl
                    )
        return _dense(spec.hidden, "o", spec, self.dtype)(out.reshape(b, t, h * hd))


class _Kernel(nn.Module):
    """One weight named ``kernel`` (the optimizer decays leaves of that
    name), for a layer whose products are not ``nn.Dense``'s."""

    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param(
            "kernel", nn.initializers.normal(0.02), self.shape, jnp.float32
        )


class ExpertMlp(nn.Module):
    """The routed FFN: a router over all ``spec.experts`` in float32, no
    capacity and no dropped token, and the part of the output that the
    ``spec.experts_held`` experts from ``spec.first_expert`` on give
    (``ops/moe.held_experts_ffn``). The router reads ``x``, the experts'
    input, or ``route_on`` where the block hands it another (``spec.
    route_before_attention``: attention's normed input, so that the
    routing hangs on nothing attention computes and the step's schedule
    may place it, and a fetch of the chosen experts, beside attention)."""

    spec: DecoderSpec
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, route_on=None):
        spec = self.spec
        b, t, d = x.shape
        held, f = spec.experts_held, spec.ffn_dim
        router = _Kernel((d, spec.experts), name="router")()
        w1 = _Kernel((held, d, f), name="w1")()
        w3 = _Kernel((held, d, f), name="w3")()
        w2 = _Kernel((held, f, d), name="w2")()
        flat = read = x.reshape(b * t, d)
        if route_on is not None:
            read = route_on.reshape(b * t, d)
            obs.counter("moe.route.before_attention", tokens=b * t, experts=spec.experts)
        with jax.named_scope(moe_ops.ROUTE):
            logits = jnp.matmul(
                read.astype(jnp.float32), router,
                precision=jax.lax.Precision.HIGHEST,
            )
            routed = moe_ops.route_top_k(logits, spec.experts_per_token)
        obs.counter(
            "moe.impl.ragged_dot", tokens=b * t, experts=spec.experts, held=held,
            first=spec.first_expert, per_token=spec.experts_per_token,
            activation=spec.activation,
        )
        y, drawn = moe_ops.held_experts_ffn(
            flat, routed, w1, w3, w2, first=spec.first_expert,
            num_experts=spec.experts, activation=spec.activation,
        )
        # kept only by a caller that asks for "intermediates" (the
        # benchmark's comparison of choices); a train step does not
        self.sow("intermediates", "experts", routed.experts)
        self.sow(
            STATS, "moe.rows_live_share",
            moe_ops.rows_live_share(
                drawn, b * t * spec.experts_per_token, spec.experts
            ),
        )
        drawn = drawn.astype(jnp.float32)
        self.sow(STATS, "moe.pairs_local", jnp.sum(drawn))
        self.sow(
            STATS, "moe.expert_load_max_over_mean",
            jnp.max(drawn) / jnp.maximum(jnp.mean(drawn), 1.0),
        )
        return y.reshape(b, t, d)


class SpecBlock(nn.Module):
    spec: DecoderSpec
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    kind: LayerKind = LayerKind()

    @nn.compact
    def __call__(self, x, positions, train: bool = True):
        spec = self.spec
        u = _norm(spec, "ln1")(x)  # float32
        a = SpecAttention(
            spec, self.dtype, self.attn_impl, self.kind, name="attn"
        )(u.astype(self.dtype), positions)
        with jax.named_scope(RESIDUAL):
            x = x + a
        y = _norm(spec, "ln2")(x).astype(self.dtype)
        if spec.ffn == "moe":
            m = ExpertMlp(spec, self.dtype, name="mlp")(
                y, route_on=u if spec.route_before_attention else None
            )
        else:
            m = MlpBlock(spec.ffn_dim, self.dtype, name="mlp")(y, train)
        with jax.named_scope(RESIDUAL):
            return x + m


class SpecDecoder(nn.Module):
    """Token ids ``[B, T]`` -> logits in the compute dtype: ``[B, T,
    vocab]``, or under block diffusion ``[B, T/2, vocab]`` (module
    docstring). Parameters are float32, products in ``dtype`` with
    float32 accumulation, norms, softmaxes and the router in float32."""

    spec: DecoderSpec
    vocab_size: int = 32_000
    max_seq_len: int = 32_768
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        spec = self.spec
        if spec.ffn == "moe" and not (
            0 <= spec.first_expert
            and spec.first_expert + spec.experts_held <= spec.experts
            and 0 < spec.experts_per_token <= spec.experts
        ):
            raise ValueError(f"no such share of the experts: {spec}")
        if spec.block_len and any(k.window for k in spec.pattern):
            raise ValueError("the block-diffusion mask takes no window")
        b, t = tokens.shape
        length = t // 2 if spec.block_len else t
        if length > self.max_seq_len:
            raise ValueError(f"sequence {length} exceeds max_seq_len {self.max_seq_len}")
        positions = jnp.arange(length)
        if spec.block_len:
            positions = jnp.concatenate([positions, positions])
        embed = self.param(
            "tok_embed", nn.initializers.normal(0.02),
            (self.vocab_size, spec.hidden), jnp.float32,
        )
        with jax.named_scope(EMBED):
            x = embed[tokens].astype(self.dtype)
            if spec.positions == "learned":
                pos = self.param(
                    "pos_embed", nn.initializers.normal(0.02),
                    (1, self.max_seq_len, spec.hidden), jnp.float32,
                )
                x = x + pos[0, positions][None].astype(self.dtype)
        block = SpecBlock
        if self.remat:
            block = nn.remat(SpecBlock, static_argnums=(3,))  # `train`
        for i in range(spec.layers):
            kind = spec.kind(i)
            obs.counter(
                "decoder.layer." + (
                    "block_diffusion" if spec.block_len
                    else "window" if kind.window else "full"
                ),
                layer=i, window=kind.window, rope=kind.rope,
            )
            x = block(
                spec, self.dtype, self.attn_impl, kind, name=f"block{i}"
            )(x, positions, train)
        x = _norm(spec, "ln_final")(x)
        if spec.block_len:
            x = x[:, :length]  # the head reads the noised half alone
        x = x.astype(self.dtype)
        if not spec.tied_head:
            return nn.Dense(
                self.vocab_size, use_bias=False, dtype=self.dtype,
                param_dtype=jnp.float32,
                kernel_init=nn.initializers.normal(0.02), name=HEAD,
            )(x)
        with jax.named_scope(HEAD):
            return jnp.einsum(
                "btd,vd->btv", x, embed.astype(self.dtype),
                preferred_element_type=jnp.float32,
            ).astype(self.dtype)


def build(name: str, *, num_classes: int = 32_000, dtype=jnp.bfloat16,
          attn_impl: str = "auto", remat: bool = False,
          max_seq_len: int = 32_768, **share: Any) -> SpecDecoder:
    """``SPECS[name]`` with the run's share of it: any field of the spec
    (``layers``, ``experts_held``, ``first_expert``, ``block_len`` ...)."""
    return SpecDecoder(
        dataclasses.replace(SPECS[name], **share), vocab_size=num_classes,
        max_seq_len=max_seq_len, dtype=dtype, attn_impl=attn_impl, remat=remat,
    )
