"""Mixture-of-Experts layer family — the expert-parallel (EP) tier.

Not in the reference (its README scopes to sync data parallelism,
``/root/reference/README.md:14-21``); this framework treats expert
parallelism as a first-class mesh axis the way SURVEY.md §2b's table
plans for. The design is GShard/Switch-style capacity routing, built
TPU-first:

* **Dense einsum dispatch** — routing is expressed as two one-hot
  einsum contractions (``dispatch``/``combine`` tensors), not gather /
  scatter: every shape is static, everything lands on the MXU, and the
  top-k loop is unrolled (k is tiny). No data-dependent control flow.
* **EP via logical axes** — expert weights carry an ``"expert"``
  logical axis (``nn.with_logical_partitioning``); the rules table maps
  it onto the mesh's ``expert`` axis, and the dispatched activations are
  constrained to ``("expert", "batch", …)`` layout, so under the GSPMD
  engine XLA inserts the token all-to-all at exactly that boundary —
  the idiomatic TPU replacement for hand-written NCCL all-to-all.
* **Router in f32** — softmax over expert logits is numerically fragile
  in bf16; the router matmul + softmax run f32 regardless of the
  compute dtype (cheap: D×E).
* **Load-balance aux loss** is sown into the ``"losses"`` collection;
  every engine (DP shard_map, GSPMD, SP) sums sown losses into the
  total, so the layer works unchanged under any parallelism.

Token dropping: each expert processes at most ``capacity`` tokens per
group (capacity_factor × fair share); overflow tokens fall through the
residual connection untouched — standard Switch behavior, and the reason
all shapes stay static.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


def _one_hot_f32(x, n):
    return jax.nn.one_hot(x, n, dtype=jnp.float32)


class MoEMlpBlock(nn.Module):
    """Drop-in replacement for ``vit.MlpBlock``: [..., S, D] -> [..., S, D].

    ``num_selected`` experts per token (top-k, k ∈ {1, 2} typical),
    gate-weighted combine, capacity-bounded dispatch.
    """

    num_experts: int
    mlp_dim: int
    num_selected: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 1e-2
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        if self.num_experts < 1:
            raise ValueError("num_experts must be >= 1")
        k = min(self.num_selected, self.num_experts)
        b, s, d = x.shape
        e = self.num_experts
        # Per-group fair share is k*s/e; capacity_factor of headroom.
        capacity = max(int(np.ceil(k * s / e * self.capacity_factor)), 1)

        router = self.param(
            "router",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("embed", "expert")
            ),
            (d, e),
            jnp.float32,
        )
        gates = jax.nn.softmax(
            jnp.einsum("bsd,de->bse", x.astype(jnp.float32), router)
        )  # [b, s, e], f32

        # Unrolled top-k: argmax, mask out, repeat. First-choice tokens get
        # buffer priority over second-choice (GShard ordering).
        masks, chosen_gates = [], []
        g = gates
        for _ in range(k):
            idx = jnp.argmax(g, axis=-1)  # [b, s]
            m = _one_hot_f32(idx, e)  # [b, s, e]
            masks.append(m)
            chosen_gates.append(jnp.sum(gates * m, axis=-1))  # [b, s]
            g = g * (1.0 - m)

        # Position of each token in its expert's buffer: tokens earlier in
        # the group (and earlier choice rounds) fill first.
        counts_before = jnp.zeros((b, 1, e), jnp.float32)
        kept_masks, positions = [], []
        for j in range(k):
            pos_in_round = jnp.cumsum(masks[j], axis=1) - masks[j]
            loc = jnp.sum((pos_in_round + counts_before) * masks[j], axis=-1)
            counts_before = counts_before + jnp.sum(
                masks[j], axis=1, keepdims=True
            )
            keep = (loc < capacity).astype(jnp.float32)  # [b, s]
            kept_masks.append(masks[j] * keep[..., None])
            positions.append(loc.astype(jnp.int32))

        # Combine weights. k >= 2: selected gates renormalized over the
        # kept choices so the expert mixture sums to 1 (matches the
        # dense-MLP limit when all experts are identical). k == 1: the
        # RAW router probability (Switch convention) — renormalizing
        # would make the weight identically 1 and cut the router's
        # gradient through the output path, leaving only the aux loss.
        kept_gate = [
            chosen_gates[j] * jnp.sum(kept_masks[j], -1) for j in range(k)
        ]
        denom = (
            jnp.ones_like(kept_gate[0])
            if k == 1
            else jnp.maximum(sum(kept_gate), 1e-9)
        )
        # dispatch/combine: [b, s, e, c]
        dispatch = sum(
            kept_masks[j][..., None] * _one_hot_f32(positions[j], capacity)[:, :, None, :]
            for j in range(k)
        )
        combine = sum(
            (kept_gate[j] / denom)[..., None, None]
            * kept_masks[j][..., None]
            * _one_hot_f32(positions[j], capacity)[:, :, None, :]
            for j in range(k)
        )

        # Load-balance loss (Switch eq. 4): E * Σ_e f_e·P_e, where f_e is
        # the fraction of tokens whose first choice is e and P_e the mean
        # router probability — minimized at uniform routing.
        f = jnp.mean(masks[0], axis=(0, 1))
        p = jnp.mean(gates, axis=(0, 1))
        aux = self.aux_loss_weight * e * jnp.sum(f * p)
        self.sow("losses", "moe_aux_loss", aux)

        # ---- the EP boundary: tokens regroup from batch-major to
        # expert-major. Under a mesh with an "expert" axis this constraint
        # is where XLA places the all-to-all.
        expert_in = jnp.einsum(
            "bsec,bsd->ebcd", dispatch.astype(self.dtype), x.astype(self.dtype)
        )
        expert_in = nn.with_logical_constraint(
            expert_in, ("expert", "batch", None, "act_embed")
        )

        w1 = self.param(
            "w1",
            nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), ("expert", "embed", "mlp")
            ),
            (e, d, self.mlp_dim),
            jnp.float32,
        )
        b1 = self.param(
            "b1",
            nn.with_logical_partitioning(nn.initializers.zeros, ("expert", "mlp")),
            (e, self.mlp_dim),
            jnp.float32,
        )
        w2 = self.param(
            "w2",
            nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), ("expert", "mlp", "embed")
            ),
            (e, self.mlp_dim, d),
            jnp.float32,
        )
        b2 = self.param(
            "b2",
            nn.with_logical_partitioning(nn.initializers.zeros, ("expert", "embed")),
            (e, d),
            jnp.float32,
        )
        h = jnp.einsum("ebcd,edh->ebch", expert_in, w1.astype(self.dtype))
        h = nn.gelu(h + b1[:, None, None, :].astype(self.dtype))
        out = jnp.einsum("ebch,ehd->ebcd", h, w2.astype(self.dtype))
        out = out + b2[:, None, None, :].astype(self.dtype)
        out = nn.with_logical_constraint(
            out, ("expert", "batch", None, "act_embed")
        )

        y = jnp.einsum(
            "bsec,ebcd->bsd", combine.astype(self.dtype), out
        )
        return y.astype(self.dtype)


class MoEDecoderBlock(nn.Module):
    """Pre-norm decoder block with an MoE FFN (attention unchanged —
    shares ``vit.Attention`` with the dense blocks)."""

    num_heads: int
    mlp_dim: int
    num_experts: int
    num_selected: int = 2
    capacity_factor: float = 1.25
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
        from distributeddeeplearning_tpu.models.vit import Attention

        y = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x).astype(self.dtype)
        x = x + Attention(
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
        y = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x).astype(self.dtype)
        x = x + MoEMlpBlock(
            self.num_experts,
            self.mlp_dim,
            self.num_selected,
            self.capacity_factor,
            dtype=self.dtype,
            name="moe",
        )(y, train)
        return x
