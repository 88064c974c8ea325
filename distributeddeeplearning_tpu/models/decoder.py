"""A decoder built from a layer spec.

``models/transformer_lm.TransformerLM`` is one block (learned
positions, LayerNorm, a fused ``3·d`` projection over equal heads, GELU
MLP, tied head) in four sizes. The decoders people deploy differ from it
in every one of those places, so this module builds the block from a
:class:`DecoderSpec`: RMSNorm or LayerNorm with a settable epsilon,
rotary or learned positions, grouped-query heads whose width is not
``hidden / heads``, a per-head q/k norm, biases or none, a GELU MLP or
routed gated experts (SiLU or ReLU gate) of which this process holds a
share (``ops/moe.py``) or one dense gated MLP, a router that reads the
FFN's normed input or attention's, a tied or an untied head, and the
four scalars some families put on the embedding, the attention scores,
the residual branches and the logits. The layers need not be alike: a
``pattern`` of :class:`LayerKind` repeats over the depth and gives each
layer its **mixer** (softmax attention, or a Mamba-2 state-space layer:
:class:`Mamba2Mixer` over ``ops/ssm.py``), and an attention layer its
window and whether it has rotary positions.

**Two mixers, three masks.** An attention layer runs under the causal
mask (a window of 0), the causal one within a window of the last keys
(``LayerKind.window``), or the block-diffusion one (``spec.block_len``,
every layer, no window, no state-space layer). Their cores are
``ops/attention``'s (``dot_product_attention``, ``block_diffusion_
attention``), and which lowering a call takes is that module's rule
(``resolve_impl``): this model states its heads, its mask and its scale
(``spec.attn_scale``; 0: ``head_dim ** -0.5``), and that no fused QKV
projection feeds the core. A state-space layer has no mask and no
positions: a convolution over each channel's last ``ssm_conv`` values,
the chunked scan (``ops/ssm.ssd_scan``), a gate and a norm of the gated
output, between two projections.

Scope names are the ones ``transformer_lm.TRAIN_STEP_GROUPS`` reads:
module ``attn`` with ``attn_core`` inside it, the FFN module ``mlp``,
norms ``ln*``, scopes ``embed``, ``residual``, ``head``. Inside
``attn_core`` a causal layer's core runs under ``attn_window`` or
``attn_full`` (:data:`ATTN_KIND_GROUPS`). Inside ``mlp`` the expert
layer has four scopes of its own, which :data:`MOE_GROUPS` reads:
``moe_route``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``. A
state-space layer's mixer is module ``ssm`` in ``attn``'s place, with
scopes ``ssm_conv`` and ``ssm_scan`` inside it (:data:`SSM_GROUPS`);
:data:`HYBRID_STEP_GROUPS` is the step's table for a model that has
such layers.

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
    TRAIN_STEP_GROUPS,
)
from distributeddeeplearning_tpu.models.vit import ATTN_CORE, MlpBlock
from distributeddeeplearning_tpu.obs.programs import part
from distributeddeeplearning_tpu.ops import moe as moe_ops
from distributeddeeplearning_tpu.ops import ssm as ssm_ops
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


# A state-space layer's parts, inside module `ssm`: the convolution and
# the scan by their scopes, and what is left of the module (the two
# projections, the gate and its norm) as the third.
SSM_GROUPS = (
    ("ssm_scan", part(ssm_ops.SSM_SCAN)),
    ("ssm_conv", part(ssm_ops.SSM_CONV)),
    ("ssm_proj", part(ssm_ops.SSM)),
)

# The train step's parts for a model with state-space layers: the mixer
# is a part of its own before the ones every decoder has. A table beside
# `TRAIN_STEP_GROUPS` and not a seventh group in it: a reader reports a
# table's groups only where the program has them all (a table short of
# one carries another tree's names: benchmarks/programs/obs.py), so a
# group that a model without such layers lacks would silence its parts.
HYBRID_STEP_GROUPS = (("ssm", part(ssm_ops.SSM)),) + TRAIN_STEP_GROUPS


class LayerKind(NamedTuple):
    """What the layers of one model differ in. ``window``: a query sees
    its own key and the ``window − 1`` before it (0: every key up to its
    own). ``rope``: q and k get the spec's rotary positions (False: no
    positions at all in this layer). ``mixer``: what mixes the tokens,
    ``"attention"`` or ``"mamba2"`` (a state-space layer has neither
    window nor positions)."""

    window: int = 0
    rope: bool = True
    mixer: str = "attention"


MAMBA2 = LayerKind(0, False, "mamba2")


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
    positions: str = "rope"  # "rope" | "learned" | "none"
    rope_theta: float = 1e6
    qk_norm: bool = False  # per-head norm of q and k before the positions
    bias: bool = False
    # "moe": routed gated experts | "gelu": GELU MLP | "glu": one dense
    # gated MLP, W_out(act(p) * q) with [p | q] = W_in x
    ffn: str = "moe"
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
    # a state-space layer (LayerKind.mixer "mamba2"): heads x head width is
    # its inner width; B and C are `ssm_state` wide, one pair a group
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4  # taps of the causal depthwise convolution
    ssm_chunk: int = 256
    # scalars on the embedding's output, the attention scores (0: the
    # customary head_dim ** -0.5), both residual branches, and under the
    # logits (they are divided by it); at 1 (0) no operation is added
    embed_scale: float = 1.0
    attn_scale: float = 0.0
    residual_scale: float = 1.0
    logits_scale: float = 1.0

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
    # Granite 4.0-H Micro's published layers (huggingface.co/ibm-granite/
    # granite-4.0-h-micro config.json, `granitemoehybrid`): 40 layers in
    # periods of ten, Mamba-2 everywhere but at 5, 15, 25, 35, where 32
    # query heads of 64 attend over 8 key heads with no positions and the
    # scores times 1/64; 64 state-space heads of 64, state 128, one group,
    # convolution 4, chunk 256; a dense SiLU-gated MLP of 8,192 in every
    # layer; no experts; tied head; the four multipliers. What the config
    # does not give (the gate before the norm, the taps' order, softplus
    # on dt + dt_bias) is the Mamba-2 modelling code's, from memory:
    # benchmarks/configs/granite-4.0-h-micro.json `assumed`.
    "granite_4_0_h_micro": DecoderSpec(
        hidden=2048, layers=40, heads=32, kv_heads=8, head_dim=64,
        norm="rms", norm_eps=1e-5, positions="none", bias=False,
        ffn="glu", ffn_dim=8192, tied_head=True,
        pattern=(MAMBA2,) * 5 + (LayerKind(0, False),) + (MAMBA2,) * 4,
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
        ssm_conv=4, ssm_chunk=256,
        embed_scale=12.0, attn_scale=0.015625, residual_scale=0.22,
        logits_scale=8.0,
    ),
    # the same at a size for tests: one period of five with its attention
    # layer third, a chunk of 8 so that a test row spans several
    "granite_tiny": DecoderSpec(
        hidden=64, layers=5, heads=4, kv_heads=2, head_dim=16,
        norm="rms", norm_eps=1e-5, positions="none", ffn="glu", ffn_dim=96,
        tied_head=True,
        pattern=(MAMBA2,) * 2 + (LayerKind(0, False),) + (MAMBA2,) * 2,
        ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_groups=1,
        ssm_conv=4, ssm_chunk=8,
        embed_scale=12.0, attn_scale=0.0625, residual_scale=0.22,
        logits_scale=8.0,
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
                    q, k, v, block_len=spec.block_len, impl=impl,
                    scale=spec.attn_scale or None,
                )
            else:
                with jax.named_scope(ATTN_WINDOW if kind.window else ATTN_FULL):
                    out = dot_product_attention(
                        q, k, v, causal=True, window=kind.window, impl=impl,
                        scale=spec.attn_scale or None,
                    )
        return _dense(spec.hidden, "o", spec, self.dtype)(out.reshape(b, t, h * hd))


class _Conv(nn.Module):
    """The taps ``kernel [C, K]`` and ``bias [C]`` of a causal depthwise
    convolution, under one module's name."""

    channels: int
    taps: int

    @nn.compact
    def __call__(self):
        # uniform in ±1/sqrt(K): a depthwise Conv1d's customary draw
        bound = self.taps ** -0.5
        draw = lambda key, shape, dtype: jax.random.uniform(  # noqa: E731
            key, shape, dtype, -bound, bound
        )
        return (
            self.param("kernel", draw, (self.channels, self.taps), jnp.float32),
            self.param("bias", draw, (self.channels,), jnp.float32),
        )


def _dt_bias_init(key, shape, dtype):
    """The inverse softplus of a step drawn log-uniform in [1e-3, 1e-1]
    (Mamba-2's published initialisation)."""
    lo, hi = jnp.log(1e-3), jnp.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (hi - lo) + lo)
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2Mixer(nn.Module):
    """A Mamba-2 layer's mixer (arXiv:2405.21060, as the ``granitemoe
    hybrid`` family runs it), ``x [B, T, D] -> [B, T, D]``:

    ``[z | xBC | dt] = x·W_in`` (widths ``H·P | H·P + 2·G·N | H``);
    ``xBC <- SiLU(causal_conv1d(xBC))``, split ``[xs | B | C]``;
    ``Δ = softplus(dt + dt_bias)``, ``a = −exp(A_log)``;
    ``y = ssd_scan(xs, Δ, a, B, C, D)`` (``ops/ssm.py``: the state is
    float32); ``g = y ⊙ SiLU(z)``, the gate first; ``n = RMSNorm(g)``
    over all ``H·P`` channels as one group; out ``n·W_out``. No bias on
    either projection, no positions, nothing carried between rows."""

    spec: DecoderSpec
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        spec = self.spec
        b, t, _ = x.shape
        h, p = spec.ssm_heads, spec.ssm_head_dim
        g, n = spec.ssm_groups, spec.ssm_state
        inner, bc = h * p, g * n
        z, xbc, dt = jnp.split(
            _dense(2 * inner + 2 * bc + h, "in_proj", spec, self.dtype)(x),
            [inner, 2 * inner + 2 * bc], axis=-1,
        )
        taps, conv_bias = _Conv(inner + 2 * bc, spec.ssm_conv, name="conv")()
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,), jnp.float32)
        a_log = self.param(
            "A_log",
            lambda key, shape, dtype: jnp.log(
                jax.random.uniform(key, shape, dtype, 1.0, 16.0)
            ),
            (h,), jnp.float32,
        )
        skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        with jax.named_scope(ssm_ops.SSM_CONV):
            xbc = nn.silu(ssm_ops.causal_conv1d(xbc, taps, conv_bias))
        xs, b_in, c_out = jnp.split(xbc, [inner, inner + bc], axis=-1)
        with jax.named_scope(ssm_ops.SSM_SCAN):
            y = ssm_ops.ssd_scan(
                xs.reshape(b, t, h, p),
                jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log),
                b_in.reshape(b, t, g, n), c_out.reshape(b, t, g, n),
                skip, chunk=spec.ssm_chunk,
            ).reshape(b, t, inner)
        gated = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
        normed = RMSNorm(spec.norm_eps, name="norm")(gated)
        return _dense(spec.hidden, "out_proj", spec, self.dtype)(
            normed.astype(self.dtype)
        )


class GatedMlp(nn.Module):
    """The dense gated FFN: ``[p | q] = x·W_in`` (``2·ffn_dim`` wide, one
    product), out ``(act(p) ⊙ q)·W_out``."""

    spec: DecoderSpec
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        spec = self.spec
        act = {"silu": nn.silu, "relu": nn.relu}[spec.activation]
        pq = _dense(2 * spec.ffn_dim, "w_in", spec, self.dtype)(x)
        return _dense(spec.hidden, "w_out", spec, self.dtype)(
            act(pq[..., :spec.ffn_dim]) * pq[..., spec.ffn_dim:]
        )


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


def _scaled(x, by: float):
    """``x · by`` in ``x``'s dtype; at 1 no operation at all."""
    return x if by == 1.0 else x * jnp.asarray(by, x.dtype)


class SpecBlock(nn.Module):
    spec: DecoderSpec
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    kind: LayerKind = LayerKind()

    @nn.compact
    def __call__(self, x, positions, train: bool = True):
        spec = self.spec
        u = _norm(spec, "ln1")(x)  # float32
        if self.kind.mixer == "mamba2":
            a = Mamba2Mixer(spec, self.dtype, name=ssm_ops.SSM)(u.astype(self.dtype))
        else:
            a = SpecAttention(
                spec, self.dtype, self.attn_impl, self.kind, name="attn"
            )(u.astype(self.dtype), positions)
        with jax.named_scope(RESIDUAL):
            x = x + _scaled(a, spec.residual_scale)
        y = _norm(spec, "ln2")(x).astype(self.dtype)
        if spec.ffn == "moe":
            m = ExpertMlp(spec, self.dtype, name="mlp")(
                y, route_on=u if spec.route_before_attention else None
            )
        elif spec.ffn == "glu":
            m = GatedMlp(spec, self.dtype, name="mlp")(y)
        else:
            m = MlpBlock(spec.ffn_dim, self.dtype, name="mlp")(y, train)
        with jax.named_scope(RESIDUAL):
            return x + _scaled(m, spec.residual_scale)


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
        kinds = [spec.kind(i) for i in range(spec.layers)]
        if any(k.mixer not in ("attention", "mamba2") for k in kinds):
            raise ValueError(f"no such mixer: {spec.pattern}")
        if any(k.mixer == "mamba2" for k in kinds):
            if spec.block_len:
                raise ValueError("a state-space layer runs under no block-diffusion mask")
            if any(k.mixer == "mamba2" and (k.window or k.rope) for k in kinds):
                raise ValueError("a state-space layer has no window and no positions")
            if not (
                spec.ssm_heads > 0 and spec.ssm_head_dim > 0 and spec.ssm_state > 0
                and spec.ssm_groups > 0 and spec.ssm_heads % spec.ssm_groups == 0
                and spec.ssm_conv > 0 and spec.ssm_chunk > 0
            ):
                raise ValueError(f"no such state-space layer: {spec}")
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
            x = _scaled(embed[tokens].astype(self.dtype), spec.embed_scale)
            if spec.positions == "learned":
                pos = self.param(
                    "pos_embed", nn.initializers.normal(0.02),
                    (1, self.max_seq_len, spec.hidden), jnp.float32,
                )
                x = x + pos[0, positions][None].astype(self.dtype)
        block = SpecBlock
        if self.remat:
            block = nn.remat(SpecBlock, static_argnums=(3,))  # `train`
        for i, kind in enumerate(kinds):
            if kind.mixer == "mamba2":
                obs.counter(
                    "decoder.layer.mamba2", layer=i, heads=spec.ssm_heads,
                    state=spec.ssm_state, chunk=spec.ssm_chunk,
                )
            else:
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
            logits = nn.Dense(
                self.vocab_size, use_bias=False, dtype=self.dtype,
                param_dtype=jnp.float32,
                kernel_init=nn.initializers.normal(0.02), name=HEAD,
            )(x)
        with jax.named_scope(HEAD):
            if spec.tied_head:
                logits = jnp.einsum(
                    "btd,vd->btv", x, embed.astype(self.dtype),
                    preferred_element_type=jnp.float32,
                )
            return _scaled(logits, 1.0 / spec.logits_scale).astype(self.dtype)


def build(name: str, *, num_classes: int = 32_000, dtype=jnp.bfloat16,
          attn_impl: str = "auto", remat: bool = False,
          max_seq_len: int = 32_768, **share: Any) -> SpecDecoder:
    """``SPECS[name]`` with the run's share of it: any field of the spec
    (``layers``, ``experts_held``, ``first_expert``, ``block_len`` ...)."""
    return SpecDecoder(
        dataclasses.replace(SPECS[name], **share), vocab_size=num_classes,
        max_seq_len=max_seq_len, dtype=dtype, attn_impl=attn_impl, remat=remat,
    )
