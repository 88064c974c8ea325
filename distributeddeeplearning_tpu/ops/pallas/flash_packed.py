"""Fused packed-QKV attention for short sequences (the ViT regime).

The streaming flash kernel (``ops/pallas/flash.py``) wins at long T where
the ``[T, T]`` score matrix cannot live on-chip; at ViT's T=197 it was
measured *slower* than XLA (PROFILE.md): block padding dominates and the
BTHD transposes it then needed around the custom call cost more than
the kernel saved. The XLA einsum path is not good either — the round-3
trace showed ~165 ms of a 275 ms ViT-B/16 step inside attention: the
``[B, H, T, T]`` f32 score tensors in HBM, einsums running at 20-40
TFLOP/s (T=197 pads badly onto (8, 128) tiles, d=64 half-fills the MXU
contraction), and ~36 ms of pure layout copies for the
``[B, T, 3, H, d]`` reshape/slice/transpose around the fused QKV
projection.

This kernel removes all three at once by changing the *boundary*:

* **Input is the QKV projection's raw output** ``[B, T, 3·H·d]`` — no
  reshape, no slicing, no transpose, no padding in XLA at all. The
  kernel reads q/k/v head columns directly via three block views of the
  same array (the packed column order ``part·H·d + h·d + i`` is exactly
  what ``reshape(..., 3, H, d)`` means, so checkpoints are unaffected),
  and masks the ragged sequence tail in-register instead of requiring a
  padded operand. Output is ``[B, T, H·d]`` — directly the proj Dense's
  input.
* **Whole sequence per program, several samples per program**: grid
  ``(B/nb, H/hp[, part])`` where ``hp`` heads (``hp·d = 128`` lanes)
  share the lane dim and ``nb`` batch samples amortise per-program
  dispatch/DMA overhead (the first cut ran one (b, h-pair) per program:
  1536 programs × ~12 µs dispatch ≈ the whole kernel runtime). Scores
  ``[T, T]`` live only in VMEM/registers — nothing ``O(T²)`` touches
  HBM.
* **LSE-free backward**: at small T recomputing the softmax costs a few
  MFLOP per program, so the backward takes only (qkv, out, d_out) and
  recomputes scores in-VMEM — no saved statistics. Its three gradient
  parts are written into ONE packed ``[B, T, 3·H·d]`` output (the
  layout the QKV projection's backward consumes) by a third, sequential
  grid axis that revisits the same resident blocks: part 0 computes
  dq/dk/dv into VMEM scratch, parts 0/1/2 store them — no XLA concat.

Chosen by ``ops/attention.resolve_impl`` (``attn_impl="auto"``) for
T ≤ ``MAX_T`` on TPU; the streaming kernel (``flash.py``, from
``flash.MIN_T`` on) and the XLA einsum are the other regimes'
implementations (``ops/attention.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributeddeeplearning_tpu.ops.pallas.flash import (  # shared helpers
    _NEG_INF,
    _ceil_to,
    _vma,
)

_LANES = 128
# Whole-[T, T]-in-VMEM is the design: ~6 live f32 score-shaped
# intermediates in the backward cost 6·T²·4 B — 6.3 MB at T=512, 25 MB
# (over the 16 MB scoped-VMEM limit) at T=1024. Longer sequences belong
# to the streaming kernel (ops/pallas/flash.py).
MAX_T = 512
_VMEM_BUDGET = 13 * 2**20  # headroom under the 16 MB scoped-VMEM limit


def heads_per_block(head_dim: int) -> int:
    """How many heads share one 128-lane block (1 for head_dim ≥ 128)."""
    return max(1, _LANES // head_dim)


def _bwd_vmem_bytes(
    nb: int, tp: int, width: int = _LANES, itemsize: int = 2
) -> int:
    """Backward-pass scoped-VMEM estimate (the fwd needs strictly less):
    5 double-buffered input blocks + the double-buffered output +
    3 scratch blocks (all at the activation ``itemsize`` — scratch
    follows ``qkv.dtype``) + ~6 live [T, T] f32 score intermediates,
    with 30 % slack for Mosaic temporaries. ``width`` is the block lane
    width hp·d (= 128 for d ≤ 128; = d for wider heads). Calibration
    (bf16, f32 scratch as originally shipped): nb=16 at Tp=208/width=128
    computed 16.4 MB pre-slack and Mosaic measured 16.2 MB (over the
    limit); nb=8 fits. bf16 scratch measured perf-neutral with identical
    final precision (one f32→bf16 rounding either way)."""
    rows = nb * tp * width
    blocks = (5 * 2 + 2 + 3) * rows * itemsize
    scores = 6 * tp * tp * 4
    return int((blocks + scores) * 1.3)


def _batch_per_block(
    batch: int, seq_len: int, width: int = _LANES, itemsize: int = 2
) -> int:
    """Samples per program: enough to amortise per-program dispatch/DMA
    overhead (1 sample/program measured ~12 µs-dominated), small enough
    that the backward stays under the scoped-VMEM limit."""
    tp = _ceil_to(seq_len, 16)
    for nb in (8, 4, 2, 1):
        if batch % nb == 0 and (
            _bwd_vmem_bytes(nb, tp, width, itemsize) <= _VMEM_BUDGET
        ):
            return nb
    return 1


def supports(seq_len: int, num_heads: int, head_dim: int) -> bool:
    """Shape eligibility for the packed kernel (caller also gates on
    backend): short sequences, head groups filling whole 128-lane blocks."""
    hp = heads_per_block(head_dim)
    return (
        seq_len <= MAX_T
        and num_heads % hp == 0
        and (head_dim % _LANES == 0 or _LANES % head_dim == 0)
        and _bwd_vmem_bytes(1, _ceil_to(seq_len, 16), hp * head_dim)
        <= _VMEM_BUDGET
    )


def _zero_tail(x, t_len: int):
    """Zero rows ≥ t_len. The kernels run on UNPADDED operands — the
    ragged tail of the last (and only) T block is whatever the DMA
    brought in, possibly inf/NaN bit patterns. A single poisoned row
    would contaminate every contraction over T (0·NaN = NaN), so every
    loaded tile is sanitised once; tail rows of outputs are then exactly
    zero and the ragged store mask drops them."""
    if t_len == x.shape[0]:
        return x
    rows = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(rows < t_len, x, jnp.zeros_like(x))


def _masked_softmax(s, t_len: int, causal: bool):
    """Row softmax over masked scores; returns (p, l_safe) with p = 0 on
    masked entries and l clamped so fully-masked (ragged-tail) rows
    divide to zero instead of NaN — the tail never reaches HBM (masked
    stores) but must not poison in-register values."""
    tq, tk = s.shape
    k_idx = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    q_idx = lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    mask = jnp.logical_and(k_idx < t_len, q_idx < t_len)
    if causal:
        mask = jnp.logical_and(mask, q_idx >= k_idx)
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    # Tail rows are all _NEG_INF: exp(s - m) would give exp(0) = 1 there;
    # force p = 0 so every downstream product/sum of the tail is zero.
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return p, jnp.where(l == 0.0, 1.0, l)


def _head_dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, t_len, nb, hp, d):
    for n in range(nb):
        outs = []
        for h in range(hp):
            cols = slice(h * d, (h + 1) * d)
            q = q_ref[n][:, cols]
            k = k_ref[n][:, cols]
            v = _zero_tail(v_ref[n][:, cols], t_len)
            s = _head_dot(q, k, ((1,), (1,))) * scale
            p, l = _masked_softmax(s, t_len, causal)
            acc = _head_dot(p.astype(v.dtype), v, ((1,), (0,)))
            outs.append(acc / l)
        o = outs[0] if hp == 1 else jnp.concatenate(outs, axis=1)
        o_ref[n] = o.astype(o_ref.dtype)


def _bwd_kernel(
    q_ref, k_ref, v_ref, o_ref, do_ref, dqkv_ref, dq_scr, dk_scr, dv_scr,
    *, scale, causal, t_len, nb, hp, d,
):
    """Recompute-softmax backward. With P = softmax(s):
    dS = P ⊙ (dP − Δ)·scale, Δ = rowsum(do ⊙ o); dq = dS·k, dk = dSᵀ·q,
    dv = Pᵀ·do. The sequential minor grid axis (part ∈ {q, k, v}) stores
    one third of the packed gradient per step from VMEM scratch; the
    input blocks don't move across parts, so everything is computed once
    at part 0."""
    part = pl.program_id(2)

    @pl.when(part == 0)
    def _compute():
        for n in range(nb):
            dqs, dks, dvs = [], [], []
            for h in range(hp):
                cols = slice(h * d, (h + 1) * d)
                q = _zero_tail(q_ref[n][:, cols], t_len)
                k = _zero_tail(k_ref[n][:, cols], t_len)
                v = _zero_tail(v_ref[n][:, cols], t_len)
                o = _zero_tail(o_ref[n][:, cols], t_len)
                do = _zero_tail(do_ref[n][:, cols], t_len)
                s = _head_dot(q, k, ((1,), (1,))) * scale
                p, l = _masked_softmax(s, t_len, causal)
                pn = p / l  # true probs, f32
                delta = jnp.sum(
                    do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True,
                )
                dp = _head_dot(do, v, ((1,), (1,)))
                ds = (pn * (dp - delta) * scale).astype(q.dtype)
                dqs.append(_head_dot(ds, k, ((1,), (0,))))
                dks.append(_head_dot(ds, q, ((0,), (0,))))
                dvs.append(_head_dot(pn.astype(do.dtype), do, ((0,), (0,))))
            cat = lambda xs: xs[0] if hp == 1 else jnp.concatenate(xs, axis=1)
            dq_scr[n] = cat(dqs).astype(dq_scr.dtype)
            dk_scr[n] = cat(dks).astype(dk_scr.dtype)
            dv_scr[n] = cat(dvs).astype(dv_scr.dtype)

    for i, scr in enumerate((dq_scr, dk_scr, dv_scr)):
        @pl.when(part == i)
        def _store(scr=scr):
            for n in range(nb):
                dqkv_ref[n] = scr[n].astype(dqkv_ref.dtype)


def _qkv_specs(nb, tp, w, num_groups, with_part_axis):
    """(q, k, v) block views of the packed [B, T, 3·H·d] array: the part
    offset is folded into the block index on the last axis."""
    if with_part_axis:
        maps = [
            lambda b, g, part, off=p, G=num_groups: (b, 0, off * G + g)
            for p in range(3)
        ]
    else:
        maps = [
            lambda b, g, off=p, G=num_groups: (b, 0, off * G + g)
            for p in range(3)
        ]
    return [pl.BlockSpec((nb, tp, w), m) for m in maps]


def _geometry(qkv, heads):
    b, t, three_hd = qkv.shape
    hd = three_hd // 3
    d = hd // heads
    hp = heads_per_block(d)
    w = hp * d
    nb = _batch_per_block(b, t, w, qkv.dtype.itemsize)
    return b, t, hd, d, hp, w, heads // hp, nb


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _packed_attention(qkv, heads, causal, scale, interpret):
    out, _ = _packed_fwd(qkv, heads, causal, scale, interpret)
    return out


def _packed_fwd(qkv, heads, causal, scale, interpret):
    b, t, hd, d, hp, w, groups, nb = _geometry(qkv, heads)
    tp = _ceil_to(t, 16)  # block T: bf16 sublane tile is 16 (f32: 8)
    vma = _vma(qkv)
    out = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, t_len=t, nb=nb, hp=hp, d=d
        ),
        grid=(b // nb, groups),
        in_specs=_qkv_specs(nb, tp, w, groups, False),
        out_specs=pl.BlockSpec((nb, tp, w), lambda b, g: (b, 0, g)),
        out_shape=jax.ShapeDtypeStruct((b, t, hd), qkv.dtype, vma=vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(qkv, qkv, qkv)
    return out, (qkv, out)


def _packed_fwd_rule(qkv, heads, causal, scale, interpret):
    return _packed_fwd(qkv, heads, causal, scale, interpret)


def _packed_bwd_rule(heads, causal, scale, interpret, res, do):
    qkv, out = res
    b, t, hd, d, hp, w, groups, nb = _geometry(qkv, heads)
    tp = _ceil_to(t, 16)
    vma = _vma(qkv, do)
    io_spec = pl.BlockSpec((nb, tp, w), lambda b, g, part: (b, 0, g))
    dqkv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, causal=causal, t_len=t, nb=nb, hp=hp, d=d
        ),
        grid=(b // nb, groups, 3),
        in_specs=_qkv_specs(nb, tp, w, groups, True) + [io_spec, io_spec],
        out_specs=pl.BlockSpec(
            (nb, tp, w), lambda b, g, part, G=groups: (b, 0, part * G + g)
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, 3 * hd), qkv.dtype, vma=vma),
        # Scratch at the INPUT dtype: for bf16 activations the eventual
        # output rounds f32→bf16 exactly once either way (perf-neutral,
        # half the scratch VMEM — measured); f32 inputs keep f32 grads.
        scratch_shapes=[
            pltpu.VMEM((nb, tp, w), qkv.dtype) for _ in range(3)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qkv, qkv, qkv, out, do)
    return (dqkv,)


_packed_attention.defvjp(_packed_fwd_rule, _packed_bwd_rule)


def fused_qkv_attention(
    qkv: jnp.ndarray,
    num_heads: int,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Multi-head attention over a packed ``[B, T, 3·H·d]`` QKV tensor.

    Returns ``[B, T, H·d]``. Column order matches
    ``qkv.reshape(B, T, 3, H, d)`` — i.e. exactly the layout the XLA path
    (``models/vit.py`` ``Attention``) slices, so the two paths share
    params and checkpoints. Use :func:`supports` to check shape
    eligibility first.
    """
    if qkv.ndim != 3:
        raise ValueError(f"expected packed [B, T, 3*H*d], got {qkv.shape}")
    b, t, three_hd = qkv.shape
    if three_hd % (3 * num_heads):
        raise ValueError(f"last dim {three_hd} not divisible by 3·{num_heads}")
    d = three_hd // 3 // num_heads
    if not supports(t, num_heads, d):
        raise ValueError(
            f"unsupported shape for packed attention: T={t}, H={num_heads}, "
            f"d={d} (need T ≤ {MAX_T}, whole 128-lane head groups)"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = float(scale) if scale is not None else d**-0.5
    return _packed_attention(qkv, num_heads, causal, scale, interpret)
