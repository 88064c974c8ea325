"""Fused paged/dense decode attention as a Pallas TPU kernel.

The serving tier's decode hot path (``models/vit.Attention`` with
``decode=True``) was the one tier still stitched from stock XLA ops:
gather K/V through the block table into a **full-sequence-length HBM
buffer**, dequantize that copy, then run masked scores over it — the
exact memory round-trip the paged layout was built to avoid
(PagedAttention) and the exact fusion online softmax eliminates
(FlashAttention). This kernel replaces the stitched chain with one
program per cache row, all heads at once:

* walk the slot's **block table** (scalar-prefetched into SMEM so the
  table drives the K/V BlockSpec index maps — the gather never
  materializes),
* stream each K/V block through VMEM in its **storage dtype** (bf16 /
  int8 / fp8) and dequantize **in-register** (``q·scale`` broadcast),
* accumulate the **online-softmax** masked attention with per-row
  positions — covering the dense row layout, the paged pool, the
  trash-block-0 convention, and the speculative ``[S, K+1]`` verify
  view with one kernel body.

Numerics mirror ``Attention._masked_decode_scores``: queries are
pre-scaled by ``head_dim**-0.5``, masked lanes take
``jnp.finfo(f32).min``, the softmax state is f32 throughout. The
score and softmax sums are re-associated, so fused-vs-XLA logits agree
to a few ULPs of the logits' scale — the greedy token-stream parity
the serve_bench gate checks rides on that
(``tests/test_paged_decode_kernel.py``).

Masking subsumes the paged trash-block convention for free: an
unallocated logical block's table entry points at block 0, but every
logical position it would contribute lies beyond the row's ``q_pos``,
so its (finite — the trash block only ever holds quantized writes)
values meet a zero softmax weight.

On non-TPU backends the kernel runs in Pallas interpreter mode, so the
CPU test/CI tier exercises the identical code path; calls are wrapped
in ``jax.named_scope(FUSED_SCOPE)`` so lowered programs carry an
auditable marker either way (``analysis/hlo_audit.py`` fused-decode
rule — on TPU the Mosaic custom-call itself is the marker).

Layout: ``q`` is ``[B, t, H, d]`` (framework-wide BTHD); the dense
cache is ``[B, L, H, d]``; the paged pool is ``[nb, bs, H, d]`` with an
int32 ``[B, mb]`` block table; quantized tiers add f32 scales with a
size-1 tail axis (``ops/quant.py``'s broadcast contract).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Marker the serving integration wraps kernel calls in; the HLO audit
# greps lowered decode programs for it (interpret-mode lowering has no
# custom-call to look for).
FUSED_SCOPE = "paged_decode_fused"

# Query rows per cache row the kernel serves: plain decode (1) and the
# speculative verify window (spec_k + 1). Rows are a static unroll over
# VPU work; a prefill-sized window — every prefill bucket is wider than
# this — is MXU work and stays on the XLA path.
MAX_QUERY_ROWS = 8

# Scratch init: large-negative instead of -inf keeps exp() NaN-free.
_NEG_INF = -1e30

# Masked score value — jnp.finfo(f32).min, matching the XLA path's
# `jnp.where(mask, scores, jnp.finfo(jnp.float32).min)` bit for bit.
_MASK_VALUE = float(jnp.finfo(jnp.float32).min)


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pick_block(pref: int, t: int) -> int:
    """Largest K block ≤ ``pref`` that minimises trailing padding
    (same policy as ``flash.py``)."""
    if t <= 128:
        return min(pref, _ceil_to(t, 8))
    cands = []
    c = max(pref, 128)
    while c >= 128:
        cands.append(c)
        c //= 2
    return min(cands, key=lambda c: (_ceil_to(t, c), -c))


def _decode_kernel(*refs, scale: float, kv_len: int, block_k: int,
                   quant: bool, paged: bool):
    """One ``(row, k-block)`` program, K innermost, all heads at once.

    ``refs`` order (static per instantiation): an SMEM block-table ref
    leads iff ``paged``; then the SMEM ``q_pos`` ref, q, k, v,
    [k_scale, v_scale iff quant], the output, and the m/l/acc VMEM
    scratch. The online-softmax state persists across the sequential K
    dimension exactly as in ``flash.py``.

    The cache keeps ``(H, d)`` as its two minor dims, so one key
    position is one ``[H, d]`` tile and a single head is a sublane of
    every tile: Mosaic cannot block on it (a block's second-minor dim
    must be a multiple of 8 or the whole axis). The kernel therefore
    takes whole ``[block_k, H, d]`` blocks and never separates heads —
    scores are a lane reduction of ``k * q`` and the weighted sum a
    reduction over the block's major dim, per query row. That is VPU
    work, which a decode step (few query rows, bandwidth-bound) can
    afford; prefill-sized ``t`` belongs to the XLA einsum
    (``MAX_QUERY_ROWS``).
    """
    refs = list(refs)
    if paged:
        refs.pop(0)  # table ref: consumed by the index maps, not here
    pos_ref, q_ref, k_ref, v_ref = refs[:4]
    ks_ref = vs_ref = None
    i = 4
    if quant:
        ks_ref, vs_ref = refs[4:6]
        i = 6
    o_ref, m_scr, l_scr, acc_scr = refs[i:i + 4]

    row = pl.program_id(0)
    j = pl.program_id(1)
    t = q_ref.shape[1]
    dtype = q_ref.dtype

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # A block wholly past every query row's position (or past kv_len)
    # is fully masked: p = 0 and the state is unchanged, so skip it.
    last = pos_ref[row, 0]
    for r in range(1, t):
        last = jnp.maximum(last, pos_ref[row, r])
    k_start = j * block_k

    @pl.when(jnp.logical_and(k_start <= last, k_start < kv_len))
    def _compute():
        kf = k_ref[0].astype(jnp.float32)  # [block_k, H, d]
        vf = v_ref[0].astype(jnp.float32)
        if quant:
            # Dequantize in-register (scales are [block_k, H, 1]): the
            # full-length HBM round-trip the stitched path paid is
            # exactly what never happens here. Rounded through the
            # compute dtype like quant.dequantize_store.
            kf = (kf * ks_ref[0]).astype(dtype).astype(jnp.float32)
            vf = (vf * vs_ref[0]).astype(dtype).astype(jnp.float32)
        # Logical K positions are block-major in BOTH layouts: the paged
        # grid walks the table in logical-block order, so block j always
        # covers positions [j·bs, (j+1)·bs) regardless of which physical
        # block the index map fetched.
        k_idx = k_start + lax.broadcasted_iota(jnp.int32, kf.shape[:2] + (1,), 0)
        in_len = k_idx < kv_len
        # Grid padding past kv_len reads undefined memory; the mask drops
        # those scores, and zeroing v kills the 0·NaN poisoning path.
        vf = jnp.where(in_len, vf, 0.0)
        for r in range(t):
            q = (q_ref[0, r] * scale).astype(dtype).astype(jnp.float32)
            s = jnp.sum(kf * q[None], axis=-1, keepdims=True)  # [bk, H, 1]
            mask = jnp.logical_and(in_len, k_idx <= pos_ref[row, r])
            s = jnp.where(mask, s, _MASK_VALUE)
            m_prev = m_scr[r]  # [H, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[None])
            m_scr[r] = m_new
            l_scr[r] = l_scr[r] * alpha + jnp.sum(p, axis=0)
            acc_scr[r] = acc_scr[r] * alpha + jnp.sum(p * vf, axis=0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def fused_decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    q_pos: jnp.ndarray,
    *,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    block_table: Optional[jnp.ndarray] = None,
    block_size: int = 0,
    kv_len: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused masked decode attention over a dense row cache or a paged
    block pool.

    Args:
      q: ``[B, t, H, d]`` queries in the compute dtype (``t`` is 1 for
        plain decode, ``K+1`` for the speculative verify view; at most
        ``MAX_QUERY_ROWS``).
      k_cache / v_cache: dense ``[B, L, H, d]`` or (with
        ``block_table``) the paged pool ``[nb, block_size, H, d]``, in
        the storage dtype (compute dtype, int8, or fp8).
      q_pos: ``[B, t]`` int32 absolute positions of the query rows —
        keys at positions ``> q_pos`` (and past ``kv_len``) are masked.
      k_scale / v_scale: f32 dequant scales with a size-1 tail axis
        (dense ``[B, L, H, 1]`` / paged ``[nb, block_size, H, 1]``);
        both present or both absent.
      block_table: ``[B, mb]`` int32 physical-block ids (paged layout
        only); entry 0 is the trash block.
      block_size: positions per pool block (paged layout only).
      kv_len: logical key length (dense default: ``L``; paged default:
        ``mb·block_size``).
      interpret: Pallas interpreter mode; defaults to "not on TPU".

    Returns ``[B, t, H, d]`` in ``q.dtype``.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    quant = k_scale is not None
    paged = block_table is not None
    if paged and block_size <= 0:
        raise ValueError("paged layout requires block_size > 0")
    if q_pos.ndim != 2:
        raise ValueError(
            f"q_pos must be [B, t] per-row positions, got shape "
            f"{q_pos.shape} (the fused kernel serves the vector-index "
            f"decode paths; scalar-index callers use the XLA path)"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    b, t, h, d = q.shape
    if t > MAX_QUERY_ROWS:
        raise ValueError(
            f"fused decode attention takes at most {MAX_QUERY_ROWS} query "
            f"rows per cache row, got t={t} (prefill-sized windows use "
            f"the XLA path)"
        )
    if paged:
        mb = block_table.shape[1]
        bk = block_size
        n_kb = mb
        length = mb * block_size
    else:
        length = k_cache.shape[1]
        bk = _pick_block(128, length)
        n_kb = _ceil_to(length, bk) // bk
    if kv_len is None:
        kv_len = length

    kernel = functools.partial(
        _decode_kernel, scale=float(d) ** -0.5, kv_len=kv_len,
        block_k=bk, quant=quant, paged=paged,
    )

    # Blocks keep the cache's two minor dims (H, d) whole — see the
    # kernel docstring. Index maps receive the scalar-prefetched refs
    # after the grid indices: the block table (paged) and q_pos.
    qo_spec = pl.BlockSpec((1, t, h, d), lambda bb, jj, *_: (bb, 0, 0, 0))
    if paged:
        # The scalar-prefetched table drives the K/V index maps: grid
        # step j fetches physical block table[b, j] straight into VMEM.
        def kv_idx(bb, jj, table, pos):
            return (table[bb, jj], 0, 0, 0)
    else:
        def kv_idx(bb, jj, pos):
            return (bb, jj, 0, 0)
    kv_spec = pl.BlockSpec((1, bk, h, d), kv_idx)
    scale_spec = pl.BlockSpec((1, bk, h, 1), kv_idx)

    in_specs = [qo_spec, kv_spec, kv_spec]
    args = [q, k_cache, v_cache]
    if quant:
        in_specs += [scale_spec, scale_spec]
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    prefetch = [q_pos.astype(jnp.int32)]
    if paged:
        prefetch.insert(0, block_table.astype(jnp.int32))

    with jax.named_scope(FUSED_SCOPE):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=(b, n_kb),
                in_specs=in_specs,
                out_specs=qo_spec,
                scratch_shapes=[
                    pltpu.VMEM((t, h, 1), jnp.float32),
                    pltpu.VMEM((t, h, 1), jnp.float32),
                    pltpu.VMEM((t, h, d), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, t, h, d), q.dtype),
            # K (minor) carries the online-softmax recurrence and must
            # stay sequential; rows parallelise freely.
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            ),
            interpret=interpret,
        )(*prefetch, *args)
