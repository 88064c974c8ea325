"""Flash attention as a Pallas TPU kernel.

The framework's native-tier attention (SURVEY.md §2a maps the
reference's CUDA/NCCL tier to first-party Pallas kernels). The XLA
einsum path (``ops/attention.py``) materialises the ``[T, T]`` score
matrix in HBM, forward and backward; these kernels keep one tile of it
in VMEM at a time, with the online-softmax recurrence, so memory is
``O(T·d)`` and nothing of size ``T × T`` leaves the chip.

Two Mosaic kernels under one custom VJP, of one shape: a grid
``(batch, head group, owned block, resident block)`` in which a program
owns one ``b``-row block of one operand (its accumulators live in VMEM
scratch across the last, sequential grid axis) and meets a resident
block of the other, ``sub`` blocks of ``b`` rows, as one wide tile
``[b, sub·b]`` (at most ``_TILE_ELEMS`` scores: one matmul, one pass of
the softmax, the program's fixed cost paid once; at T = 1,024 the
resident block is the whole sequence and K and V are fetched once a
head group):

  forward  : owns a q block. Running row-max ``m``, normaliser ``l`` and
             the f32 accumulator in scratch; two matmuls a tile
             (``q·kᵀ``, ``p·v``). Saves the per-row logsumexp.
  backward : owns a k/v block, meets q/do, on transposed tiles
             (``sᵀ = k·qᵀ``: keys on sublanes, queries on lanes). Each
             tile is formed once and gives all three gradients, five
             matmuls: ``pᵀ = exp(sᵀ − lse)``, ``dv = Σ pᵀ·do``,
             ``dsᵀ = pᵀ ⊙ (v·doᵀ − Δ)``, ``dk = Σ dsᵀ·q·scale`` (plain
             products, its own accumulators) and ``dq = Σ ds·k·scale``,
             the one product that contracts the tile's first axis.
             ``dq`` belongs to the resident blocks, not to the program:
             its f32 sums for the whole query side stay in VMEM scratch
             (``[heads a program, T, d]``: 0.5 MiB at GPT-2's shapes;
             with grouped queries a slot for each query head of the
             group, 16 MiB at 8 × 4,096 × 128) while the grid walks the
             k blocks, which is therefore a sequential axis too, and
             each block is written once, in the operands' dtype, in the
             last k block's pass. No partial gradient goes through HBM.

Causal attention computes no block above the diagonal: of a resident
block a q block meets the first ``c`` sub-blocks only, up to its own
(a k block: from its own on), a static branch for each ``c``, and only
the tile that holds the diagonal is masked (q and k blocks are one
size). Resident blocks wholly above the diagonal are neither computed
nor fetched. Without ``causal`` the one masked tile is the one that
holds the keys' padding.

Operands stay ``[B, T, H·d]``, as the projections wrote them: a
program's blocks are 128 lanes wide and hold ``128/d`` heads side by
side (two at d = 64; independent chains in one program), so no
transpose stands round the custom calls. Head widths that do not tile
the lanes (d = 96) are transposed to ``[B·H, T, d]`` first
(``heads_per_program``).

``lse`` and ``Δ = rowsum(do ⊙ o)`` travel as rows, ``[B, H, major, 8,
sub·b]`` (queries on lanes, 8 equal sublanes: the smallest f32 tile),
16 times smaller than lane-replicated columns; the backward kernel
reads them as they lie.

``_flash_bwd_scan`` is the kept pure-JAX reference for the backward
kernel. On non-TPU backends the kernels run in Pallas interpreter
mode, so the CPU test mesh exercises the identical code path (§7 hard
part (d)).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributeddeeplearning_tpu import obs

_NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() NaN-free
_LANES = 128  # VPU lane width: m/l scratch rows are lane-replicated
_SUBLANES = 8
# Elements of the widest score tile a program computes at once (2 MiB in
# f32): a block's rows times the rows of the other operand that are
# resident beside it, at most _MAX_SUB blocks of them (a static branch
# each). Longer sequences stream such blocks along the last grid axis.
_TILE_ELEMS = 512 * 1024
_MAX_SUB = 8
# The most of dq's sums that the backward kernel may hold beside its 32
# MiB of blocks and tiles (the v5e's VMEM is 128 MiB): 131,072 rows at
# one 128-lane block a program, 16,384 with eight query heads a key head.
_DQ_VMEM = 64 * 2**20


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _vma(*arrays):
    """Union of the inputs' varying-mesh-axes (empty outside shard_map)."""
    out = set()
    for a in arrays:
        out |= set(getattr(jax.typeof(a), "vma", ()) or ())
    return frozenset(out)


class Mask(NamedTuple):
    """Which keys a query sees, as the kernels take it: every key (the
    default), the causal rule, the causal rule within a window, or one
    of the block-diffusion objective's three block-granular rules.
    ``causal``: no key after the query's own place; resident blocks
    above the diagonal are neither computed nor fetched. ``window``
    (with ``causal``, ``gran`` 1): of those keys the query's own and the
    ``window − 1`` before it alone, a band; resident blocks wholly
    behind the band are neither computed nor fetched either, and the
    tiles that hold the band's two edges are masked. A window that
    covers the sequence is the causal rule, and ``ops/attention.
    dot_product_attention`` hands it on as that. ``gran`` and
    ``strict`` coarsen
    the diagonal tile's rule to blocks of ``gran`` positions (the
    block-diffusion objective, ``ops/attention.block_diffusion_
    attention``): a query of block β sees the keys of blocks ≤ β, or
    with ``strict`` of blocks < β alone. A kernel block is a multiple of
    ``gran`` rows, so the tiles off the diagonal stay whole or skipped.
    A row that sees no key at all (``strict``: the first ``gran`` rows)
    comes out with a logsumexp of ``_NEG_INF``: its output is weightless
    in the caller's merge, and its gradients are nought. ``own`` (not
    with ``causal``): a query sees the keys of its own block of ``gran``
    alone; a q block then meets the one k block of its own index, one
    tile a program, and no other is computed or fetched."""

    causal: bool = False
    gran: int = 1
    strict: bool = False
    own: bool = False
    window: int = 0


def _as_mask(causal) -> Mask:
    return causal if isinstance(causal, Mask) else Mask(bool(causal))


def _sees(rows, cols, mask: Mask):
    """Where query ``rows`` see key ``cols`` (one origin) under a causal
    (windowed or block-granular) or an own-block ``mask``."""
    if mask.own:
        return rows // mask.gran == cols // mask.gran
    if mask.window:
        return (cols <= rows) & (cols > rows - mask.window)
    if mask.gran == 1 and not mask.strict:
        return cols <= rows
    first_unseen = rows // mask.gran * mask.gran
    return cols < (first_unseen if mask.strict else first_unseen + mask.gran)


# The shortest sequence for which `"auto"` takes these kernels over the
# XLA einsum (`supports`). Measured on the v5e, a GPT-2 layer's attention
# core forward + backward at 16 x 12 heads, d = 64, causal, device ms
# kernel | XLA: T = 1,024: 2.92 | 6.05; 768: 2.14 | 3.09; 640: 1.88 |
# 2.27 (PERF.md section 6, PR 26). XLA's cost falls with T squared, the
# kernel's with its 128-row blocks: by those readings the einsum is
# ahead somewhere under 600, and between 513 and 639 nothing was run.
MIN_T = 640


def _pick_block(t: int) -> int:
    """Rows of a q and of a k block (one size: the diagonal tile is then
    a constant triangle): of 512, 256 and 128 the one that pads ``t``
    least, the larger on a tie. Measured at T = 1,024, d = 64 (as
    above, one call earlier): 512 -> 3.15 ms, 256 -> 3.21, 1,024 (no
    tile skipped) -> 3.43; larger tiles amortise a program's fixed cost,
    smaller ones skip more of the causal triangle."""
    return min((512, 256, 128), key=lambda c: (_ceil_to(t, c), -c))


class _Plan(NamedTuple):
    """How a length-``t`` operand is cut: blocks of ``b`` rows; walked,
    it lies in ``major`` resident blocks of ``sub`` blocks each."""

    b: int
    sub: int
    major: int

    @property
    def blocks(self) -> int:
        return self.major * self.sub

    @property
    def rows(self) -> int:  # padded length
        return self.blocks * self.b


def _plan(t: int, b: int) -> _Plan:
    n = -(-t // b)
    major = -(-n // min(_MAX_SUB, max(1, _TILE_ELEMS // (b * b))))
    return _Plan(b, -(-n // major), major)


def heads_per_program(heads: int, d: int) -> int:
    """Heads that share one program's 128-lane blocks of a ``[B, T, H·d]``
    operand (the layout the projections write: no transpose round the
    kernel), or 0 where head blocks do not tile the lanes (d = 96): the
    operands are then transposed to ``[B·H, T, d]``, one head a program."""
    if d % _LANES == 0:
        return 1
    hp = _LANES // d
    return hp if _LANES % d == 0 and heads % hp == 0 else 0


def supports(seq_len: int, num_heads: int, head_dim: int) -> bool:
    """Where these kernels are the better attention core (the caller
    also gates on backend and on local operands): sequences from
    ``MIN_T`` on, heads whose blocks tile the lanes."""
    return seq_len >= MIN_T and heads_per_program(num_heads, head_dim) > 0


def _head(x, h: int, d: int):
    """Head ``h``'s columns of a ``[rows, heads·d]`` tile."""
    return x if x.shape[1] == d else x[:, h * d : (h + 1) * d]


def _pad_rows(x, rows: int):
    return jnp.pad(x, ((0, 0), (0, rows - x.shape[1]), (0, 0)))


def _dot(a, b):
    return lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_nt(a, b):
    return lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_tn(a, b):
    """``aᵀ·b``. Mosaic takes the contraction over both first axes as it
    stands; an explicit transpose of the tile before a plain product, in
    f32 or in the operands' dtype, measured the same to 0.1%."""
    return lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _scores(a, b, scale: float):
    """``a·bᵀ·scale`` in f32 from operands in the input dtype (bf16 →
    full-rate MXU). A power-of-two scale (d = 64, 256) is folded into
    ``a`` first, which is exact and saves a pass over the tile."""
    if math.frexp(scale)[0] == 0.5:
        return _dot_nt(a * scale, b)
    return _dot_nt(a, b) * scale


def _across(x, n: int):
    """Lane-replicated ``[b, 128]`` column statistics against a
    ``[b, n]`` tile: whole vregs side by side where ``n`` allows, and no
    broadcast from one lane a vreg (the forward kernel took 0.87 ms a
    GPT-2 layer with ``x[:, :1]`` here, 0.65 so)."""
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    return x[:, :n] if n < _LANES else x[:, :1]


def _down(x, rows: int):
    """``[8, n]`` row statistics (equal sublanes) against a
    ``[rows, n]`` tile, the same way."""
    if rows % _SUBLANES == 0:
        return jnp.tile(x, (rows // _SUBLANES, 1))
    return x[:1]


def _to_rows(x):
    """Lane-replicated ``[b, 128]`` column statistics as ``[8, b]`` rows."""
    return x.T[:_SUBLANES]


def _reach(window: int, b: int) -> int:
    """How far a window's band reaches in blocks of ``b`` rows: q block
    ``i`` holds a query that sees a key of k block ``j`` iff ``i − reach
    <= j <= i``."""
    return (window + b - 2) // b


def _band_steps(owner: _Plan, walked: _Plan, window: int, owner_first: bool):
    """``(visited, skipped)``: of the ``owner.blocks × walked.major``
    steps a head's grid takes under a window, how many meet a resident
    block that holds part of the band, and how many meet none (nothing
    computed, nothing fetched)."""
    reach = _reach(window, owner.b)
    visited = 0
    for i in range(owner.blocks):
        lo, hi = (max(i - reach, 0), i) if owner_first else (
            i, min(i + reach, walked.blocks - 1))
        visited += hi // walked.sub - lo // walked.sub + 1
    return visited, owner.blocks * walked.major - visited


def _walk_band(visit, off, n_sub: int, window: int, b: int, owner_first: bool):
    """A window's walk of one resident block, for the forward kernel
    (``owner_first``: a q block against resident keys) and the backward
    (a k block against resident queries). ``off`` is the place the
    owner's own block has among the resident block's ``n_sub``
    sub-blocks (before it, inside it or past it); the band's live
    sub-blocks are ``off − reach .. off`` of the keys, ``off .. off +
    reach`` of the queries. One call ``visit(lo, hi, masked)`` for the
    live span ``[lo, hi)``, a static branch a span. A span that ends
    inside the resident block holds one of the band's edges (the
    diagonal, or the sub-block the window's far edge cuts), and so may
    the whole block: only that one has an unmasked branch too."""
    reach, clear = _reach(window, b), window // b
    if owner_first:
        first, last = off - reach, off
        whole = jnp.logical_and(off >= n_sub, off < clear)
    else:
        first, last = off, off + reach
        whole = jnp.logical_and(off < 0, off >= n_sub - clear)
    for lo in range(n_sub):
        for hi in range(lo + 1, n_sub + 1):
            here = jnp.logical_and(
                first <= 0 if lo == 0 else first == lo,
                last >= n_sub - 1 if hi == n_sub else last == hi - 1,
            )
            if (lo, hi) == (0, n_sub):
                pl.when(jnp.logical_and(here, whole))(
                    functools.partial(visit, lo, hi, False)
                )
                here = jnp.logical_and(here, jnp.logical_not(whole))
            pl.when(here)(functools.partial(visit, lo, hi, True))


def _walk_keys(step, i, g0, n_sub: int, mask: Mask, kv_len: int, b: int):
    """For a q block ``i`` against the resident K/V block that starts at
    global block ``g0``: one call ``step(c, keep)`` for the tile of its
    first ``c`` sub-blocks, the live ones (a static branch a width).
    ``keep`` masks the tile (queries on rows) where its last sub-block
    is the diagonal one or, without ``causal``, holds the keys' padding;
    a resident block wholly below the diagonal gets none. Under a
    window the live sub-blocks start where the band does:
    ``step(c, keep, lo)`` (:func:`_walk_band`)."""
    if mask.window:
        def visit(lo, hi, masked):
            keep = None
            if masked:  # positions from the q block's first row on
                rows = lax.broadcasted_iota(jnp.int32, (b, (hi - lo) * b), 0)
                cols = lax.broadcasted_iota(jnp.int32, (b, (hi - lo) * b), 1)
                keep = _sees(rows, cols + (lo - (i - g0)) * b, mask)
            step(hi, keep, lo)

        _walk_band(visit, i - g0, n_sub, mask.window, b, owner_first=True)
        return
    if mask.own:  # the resident block is the q block's own, one tile
        step(1, _sees(
            lax.broadcasted_iota(jnp.int32, (b, b), 0),
            lax.broadcasted_iota(jnp.int32, (b, b), 1), mask,
        ))
        return
    causal = mask.causal
    edge = i if causal else kv_len // b  # the one block that needs a mask
    masked = bool(causal or kv_len % b)
    ahead = edge - g0  # unmasked sub-blocks of this resident block

    @pl.when(ahead >= n_sub)
    def _whole():
        step(n_sub, None)

    for c in range(1, n_sub + 1):
        width = c if masked else c - 1
        if not width:
            continue

        @pl.when(ahead == c - 1)
        def _part(c=c, width=width):
            keep = None
            if masked:
                rows = lax.broadcasted_iota(jnp.int32, (b, c * b), 0)
                cols = lax.broadcasted_iota(jnp.int32, (b, c * b), 1)
                keep = (
                    _sees(rows + (c - 1) * b, cols, mask) if causal
                    else cols < (c - 1) * b + kv_len % b
                )
            step(width, keep)


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, mask: Mask, kv_len: int, b: int, hp: int,
):
    """One q block of ``hp`` heads against one resident K/V block. The
    heads are independent chains in one program: the scheduler runs one
    head's softmax under another's matmuls."""
    i, jm = pl.program_id(2), pl.program_id(3)
    n_sub = k_ref.shape[1] // b
    d = q_ref.shape[2] // hp

    @pl.when(jm == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(c, keep, lo=0):
        rows = slice(lo * b, c * b)
        qs, ks, vs = q_ref[0], k_ref[0, rows, :], v_ref[0, rows, :]
        for h in range(hp):
            v = _head(vs, h, d)
            s = _scores(_head(qs, h, d), _head(ks, h, d), scale)  # [b, c·b] f32
            if keep is not None:
                s = jnp.where(keep, s, _NEG_INF)
            m_prev = m_scr[h]  # [b, _LANES], lane-replicated
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _across(m_new, s.shape[1]))
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            m_scr[h] = m_new
            acc_scr[h] = acc_scr[h] * _across(alpha, d) + _dot(p.astype(v.dtype), v)

    _walk_keys(step, i, jm * n_sub, n_sub, mask, kv_len, b)

    @pl.when(jm == pl.num_programs(3) - 1)
    def _finalize():
        outs = []
        for h in range(hp):
            l = l_scr[h]
            l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows
            outs.append(acc_scr[h] / _across(l, d))
            lse_ref[0, h, 0] = _to_rows(m_scr[h] + jnp.log(l))
        o_ref[0] = jnp.concatenate(outs, axis=1).astype(o_ref.dtype)


def _flash_bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    dq_scr, dk_scr, dv_scr,
    *, scale: float, mask: Mask, kv_len: int, b: int, hp: int, major: int = 0,
):
    """All three gradients from one k/v block against one resident Q/dO
    block, on one transposed tile ``[keys, queries]`` of the sub-blocks
    from the diagonal one on (causal: the ones before it are skipped, a
    static branch a start; under a window the ones past the band too,
    a branch a span): five products, one exponential, one
    ``p ⊙ (dp − Δ)``. ``dk`` and ``dv`` are the program's own, summed
    along the last grid axis. ``dq`` is the resident blocks': one f32
    slot of ``dq_scr`` for each step of the last axis, summed over the k
    blocks (the axis before it, sequential too) and written in the pass
    of the last one (``_specs``: ``summed``). Under ``Mask.own`` the one
    tile is a q block's whole work: one slot, written at once. Padded
    query rows carry ``do = Δ = 0`` and add nothing. ``major`` (grouped
    queries): the last grid axis walks the query heads of this key
    head's group, ``major`` resident blocks each."""
    i, jt = pl.program_id(2), pl.program_id(3)
    jm = jt % major if major else jt
    n_sub = q_ref.shape[1] // b
    d = k_ref.shape[2] // hp
    slot = 0 if mask.own else jt

    @pl.when(jt == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(mask.own or i == 0)
    def _init_dq():
        dq_scr[slot] = jnp.zeros(dq_scr.shape[1:], dq_scr.dtype)

    def step(lo, keep, hi=None):
        rows = slice(lo * b, None if hi is None else hi * b)
        ks, vs = k_ref[0], v_ref[0]
        qs, dos = q_ref[0, rows, :], do_ref[0, rows, :]
        for h in range(hp):
            q, do, k = _head(qs, h, d), _head(dos, h, d), _head(ks, h, d)
            lse = _down(lse_ref[0, h, 0, :, rows], b)
            pt = jnp.exp(_scores(k, q, scale) - lse)  # [b, c·b]
            if keep is not None:
                pt = jnp.where(keep, pt, 0.0)
            dv_scr[h] += _dot(pt.astype(do.dtype), do)
            dpt = _dot_nt(_head(vs, h, d), do)
            dst = pt * (dpt - _down(delta_ref[0, h, 0, :, rows], b))
            dst = dst.astype(q.dtype)
            dk_scr[h] += _dot(dst, q)
            dq_scr[slot, h, rows, :] += _dot_tn(dst, k)

    def tile(lo, axis):
        return lax.broadcasted_iota(jnp.int32, (b, (n_sub - lo) * b), axis)

    def diagonal(lo):  # the tile's first sub-block is the diagonal one
        step(lo, _sees(tile(lo, 1), tile(lo, 0), mask))

    def band(lo, hi, masked):
        keep = None
        if masked:  # positions from the k block's first row on
            keys = lax.broadcasted_iota(jnp.int32, (b, (hi - lo) * b), 0)
            queries = lax.broadcasted_iota(jnp.int32, (b, (hi - lo) * b), 1)
            keep = _sees(queries + (lo - (i - jm * n_sub)) * b, keys, mask)
        step(lo, keep, hi)

    if mask.own:
        diagonal(0)
    elif mask.window:
        _walk_band(band, i - jm * n_sub, n_sub, mask.window, b, owner_first=False)
    elif mask.causal:
        behind = i - jm * n_sub  # q sub-blocks of this resident block before k's
        pl.when(behind < 0)(functools.partial(step, 0, None))
        for lo in range(n_sub):
            pl.when(behind == lo)(functools.partial(diagonal, lo))
    elif kv_len % b:  # the last k block holds the keys' padding
        pl.when(i < kv_len // b)(functools.partial(step, 0, None))
        pl.when(i == kv_len // b)(lambda: step(0, tile(0, 0) < kv_len % b))
    else:
        step(0, None)

    @pl.when(jt == pl.num_programs(3) - 1)
    def _finalize():
        dk = jnp.concatenate([dk_scr[h] for h in range(hp)], axis=1)
        dv = jnp.concatenate([dv_scr[h] for h in range(hp)], axis=1)
        dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(mask.own or i == pl.num_programs(2) - 1)
    def _finalize_dq():
        dq = jnp.concatenate([dq_scr[slot, h] for h in range(hp)], axis=1)
        dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _specs(owner: _Plan, walked: _Plan, w: int, hp: int, groups: int,
           causal: bool, owner_first: bool, rep: int = 1,
           diagonal: bool = False, window: int = 0):
    """Block specs of a kernel's grid ``(batch, head group, owned block,
    resident block)`` over ``[B, T, H·d]`` operands: ``own(part)`` a
    ``[b, w]`` block (``w = hp·d`` lanes), ``walk(part)`` a resident
    ``[sub·b, w]`` block, ``own_stat``/``walk_stat`` the row statistics
    of either (``[B, H, major, 8, sub·b]``). ``part`` picks q, k or v
    (0, 1, 2) where they are the thirds of one packed ``[B, T, 3·H·d]``
    array: ``groups`` lane blocks each. Causal programs that have no
    live tile in a resident block name the nearest one that has, so
    nothing is fetched for them: a q owner (``owner_first``) lives at or
    after its keys, a k owner at or before its queries; under a
    ``window`` no further than the band reaches (:func:`_reach`).

    ``rep`` > 1 (grouped queries, one head a program): ``rep`` query
    heads read one key head. A q owner's grid counts query heads and
    its resident block is key head ``g // rep``'s; a k owner's grid
    counts key heads, and its last axis walks the ``rep`` query heads of
    its group, ``walked.major`` resident blocks each. ``diagonal``
    (``Mask.own``): the resident block is the one of the owner's own
    index, whatever the last axis says (one step a walked head).

    ``summed``: a block of the walked operand's gradient, which every
    owner adds to (a k owner to ``dq``). The kernel holds the sums in
    VMEM and writes a block in the last owner's pass; until then the
    spec names the block that pass writes first, so nothing goes back to
    HBM before it holds its sum. With ``diagonal`` one owner meets a
    block, and it is written at once."""
    b = owner.b
    major = 1 if diagonal else walked.major
    reach = _reach(window, b)

    def resident(i, jm):
        if diagonal:
            return i
        if rep > 1 and not owner_first:
            jm = jm % major
        if not causal:
            return jm
        near = i // walked.sub
        jm = jnp.minimum(jm, near) if owner_first else jnp.maximum(jm, near)
        if window and owner_first:
            jm = jnp.maximum(jm, jnp.maximum(i - reach, 0) // walked.sub)
        elif window:
            jm = jnp.minimum(
                jm, jnp.minimum(i + reach, walked.blocks - 1) // walked.sub
            )
        return jm

    def walked_head(g, jm):
        """Lane block (and statistics row) of the walked operand."""
        if rep == 1:
            return g
        return g // rep if owner_first else g * rep + jm // major

    def own(part=0):
        return pl.BlockSpec(
            (1, b, w), lambda n, g, i, jm: (n, i, part * groups + g)
        )

    def walk(part=0):
        return pl.BlockSpec(
            (1, walked.sub * b, w),
            lambda n, g, i, jm: (
                n, resident(i, jm), part * groups + walked_head(g, jm)
            ),
        )

    own_stat = pl.BlockSpec(
        (1, hp, 1, _SUBLANES, b),
        lambda n, g, i, jm: (n, g, i // owner.sub, 0, i % owner.sub),
    )
    walk_stat = pl.BlockSpec(
        (1, hp, 1, _SUBLANES, walked.sub * b),
        lambda n, g, i, jm: (n, walked_head(g, jm), resident(i, jm), 0, 0),
    )

    def summed_index(n, g, i, jm):
        if diagonal:
            return n, i, walked_head(g, jm)
        jm = jnp.where(i == owner.blocks - 1, jm, 0)
        return n, jm % major, walked_head(g, jm)

    summed = pl.BlockSpec((1, walked.sub * b, w), summed_index)
    return own, walk, own_stat, walk_stat, summed


def _count_band(owner: _Plan, walked: _Plan, mask: Mask, which: str,
                owner_first: bool) -> None:
    """``attn.window.blocks`` at trace time: what a head's grid walks
    under a window (labels ``visited``, ``skipped``, ``pass``)."""
    if mask.window:
        visited, skipped = _band_steps(owner, walked, mask.window, owner_first)
        obs.counter(
            "attn.window.blocks", visited=visited, skipped=skipped,
            window=mask.window, block=owner.b, **{"pass": which},
        )


def _params(sequential: int, vmem_bytes: int = 32 * 2**20):
    """The last ``sequential`` grid axes carry accumulators and run in
    order; the others are free to parallelise."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (4 - sequential)
        + ("arbitrary",) * sequential,
        vmem_limit_bytes=vmem_bytes,
    )


def _geometry(q, k, heads: int, block: Optional[int], packed: bool,
              mask: Mask = Mask()):
    """``(hp, d, pq, pk, parts)`` of one call: heads a program, head
    width, the plans of the query and the key side (one block size), and
    which third of its array each of q, k, v is (``packed``: one
    ``[B, T, 3·H·d]`` array given three times)."""
    d = q.shape[2] // heads // (3 if packed else 1)
    b = block or _pick_block(max(q.shape[1], k.shape[1]))
    if b % mask.gran:
        raise ValueError(f"kernel block {b} is no multiple of the mask's {mask.gran}")
    if mask.window and (
        not mask.causal or mask.gran != 1 or mask.strict or mask.own
    ):
        raise ValueError(f"a window stands with the plain causal rule alone: {mask}")
    hp = heads_per_program(heads, d) or 1  # 0: transposed, one head an array row
    parts = (0, 1, 2) if packed else (0, 0, 0)
    if mask.own:  # a block meets its own: nothing resident beside it
        blocks = _Plan(b, 1, -(-q.shape[1] // b))
        return hp, d, blocks, blocks, parts
    return hp, d, _plan(q.shape[1], b), _plan(k.shape[1], b), parts


def _flash(q, k, v, heads, causal, scale, block, interpret, packed=False, rep=1):
    """Forward over ``[B, T, heads·d]`` operands (``packed``: q, k and v
    are one ``[B, T, 3·heads·d]`` array; ``rep`` > 1: k and v hold
    ``heads // rep`` heads, each read by ``rep`` query heads). ``causal``
    is a bool or a :class:`Mask`. Returns ``out [B, T, heads·d]`` and
    the rows' logsumexp as ``[B, heads, major, 8, sub·b]`` (module
    docstring)."""
    mask = _as_mask(causal)
    causal = mask.causal
    n, tq = q.shape[:2]
    tk = k.shape[1]
    hp, d, pq, pk, (pq_, pk_, pv_) = _geometry(q, k, heads, block, packed, mask)
    if rep > 1 and (hp != 1 or packed or d % _LANES):
        raise ValueError("grouped queries need one head a program (d % 128 == 0)")
    w, b, hd = hp * d, pq.b, heads * d
    # every block of the padded q is computed: the backward kernel reads
    # the statistics of all of them
    qp = _pad_rows(q, pq.rows)
    kp, vp = _pad_rows(k, pk.rows), _pad_rows(v, pk.rows)
    own, walk, own_stat, _, _ = _specs(
        pq, pk, w, hp, heads // hp, causal, owner_first=True, rep=rep,
        diagonal=mask.own, window=mask.window,
    )
    _count_band(pq, pk, mask, "forward", owner_first=True)
    # vma: inside shard_map (the DP/SP engines) outputs vary over the
    # same mesh axes as the inputs; check_vma requires saying so.
    vma = _vma(q, k, v)
    out, lse = pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel, scale=scale, mask=mask, kv_len=tk, b=b, hp=hp
        ),
        grid=(n, heads // hp, pq.blocks, 1 if mask.own else pk.major),
        in_specs=[own(pq_), walk(pk_), walk(pv_)],
        out_specs=[own(), own_stat],
        out_shape=[
            jax.ShapeDtypeStruct((n, pq.rows, hd), q.dtype, vma=vma),
            jax.ShapeDtypeStruct(
                (n, heads, pq.major, _SUBLANES, pq.sub * b), jnp.float32, vma=vma
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((hp, b, _LANES), jnp.float32),
            pltpu.VMEM((hp, b, _LANES), jnp.float32),
            pltpu.VMEM((hp, b, d), jnp.float32),
        ],
        compiler_params=_params(1),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :tq], lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, heads, causal, scale, block, interpret):
    out, _ = _flash(q, k, v, heads, causal, scale, block, interpret)
    return out


def _flash_fwd_rule(q, k, v, heads, causal, scale, block, interpret):
    out, lse = _flash(q, k, v, heads, causal, scale, block, interpret)
    return out, (q, k, v, out, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _flash_qkv_attention(qkv, heads, causal, scale, block, interpret):
    """The same over one packed ``[B, T, 3·heads·d]`` array, read in
    place: what the fused QKV projection writes, with no slice or
    reshape between it and the kernels."""
    out, _ = _flash(qkv, qkv, qkv, heads, causal, scale, block, interpret, True)
    return out


def _flash_qkv_fwd_rule(qkv, heads, causal, scale, block, interpret):
    out, lse = _flash(qkv, qkv, qkv, heads, causal, scale, block, interpret, True)
    return out, (qkv, out, lse)


def _flash_qkv_bwd_rule(heads, causal, scale, block, interpret, res, do):
    qkv, out, lse = res
    grads = _flash_bwd_rule(
        heads, causal, scale, block, interpret, (qkv, qkv, qkv, out, lse), do,
        packed=True,
    )
    # A concatenation of one call's three outputs XLA:TPU writes as three
    # in-place updates of a `[B, T, 3·H·d]` buffer; with dq behind a
    # barrier it fuses the concatenation into the projection's backward
    # products, as it did when dq came from a call of its own (GPT-2's
    # step: 105.7 -> 102.9 ms, 36 update fusions and 12 bias sums fewer).
    dq, dk, dv = grads
    return (jnp.concatenate([lax.optimization_barrier(dq), dk, dv], axis=-1),)


def _flash_bwd_rule(heads, causal, scale, block, interpret, res, do,
                    packed=False, rep=1, dlse=None):
    """Flash backward as one Mosaic kernel of the forward's shape
    (module docstring), counted at trace time as ``attn.bwd.fused``
    (labels ``shape``, ``rep``, ``mask``). ``_flash_bwd_scan`` below is
    the kept reference implementation (parity-tested in
    ``tests/test_attention_ops.py``). ``dlse [B, T, heads]``: the
    cotangent of the rows' logsumexp where the caller used it
    (``d lse / d s = p``, so it enters as ``−Δ``)."""
    mask = _as_mask(causal)
    causal = mask.causal
    q, k, v, out, lse = res
    n, tq = q.shape[:2]
    tk = k.shape[1]
    hp, d, pq, pk, (pq_, pk_, pv_) = _geometry(q, k, heads, block, packed, mask)
    w, b, hd = hp * d, pq.b, heads * d
    qp, dop = _pad_rows(q, pq.rows), _pad_rows(do, pq.rows)
    kp, vp = _pad_rows(k, pk.rows), _pad_rows(v, pk.rows)
    delta = jnp.sum(
        (do.astype(jnp.float32) * out.astype(jnp.float32)).reshape(n, tq, heads, d),
        axis=-1,
    )  # [n, tq, heads]
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    delta = jnp.pad(delta, ((0, 0), (0, pq.rows - tq), (0, 0)))
    delta = jnp.broadcast_to(
        delta.transpose(0, 2, 1).reshape(n, heads, pq.major, 1, -1), lse.shape
    )
    vma = _vma(q, k, v, do)
    obs.counter(
        "attn.bwd.fused", shape=list(q.shape), rep=rep, mask=mask._asdict()
    )

    own, walk, _, walk_stat, summed = _specs(
        pk, pq, w, hp, heads // hp, causal, owner_first=False, rep=rep,
        diagonal=mask.own, window=mask.window,
    )
    _count_band(pk, pq, mask, "backward", owner_first=False)
    q_major = 1 if mask.own else pq.major
    grouped = {"major": q_major} if rep > 1 else {}
    # dq's sums: a slot for each step of the last axis (under `own` one)
    dq_scr = (1 if mask.own else rep * q_major, hp, pq.sub * b, d)
    dq_bytes = 4 * math.prod(dq_scr[:3]) * _ceil_to(d, _LANES)
    if dq_bytes > _DQ_VMEM:
        raise ValueError(
            f"the flash backward keeps dq's f32 sums {dq_scr} in VMEM: "
            f"{dq_bytes} B is over its {_DQ_VMEM} B"
        )
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_kernel, scale=scale, mask=mask, kv_len=tk, b=b, hp=hp,
            **grouped,
        ),
        grid=(n, heads // hp // rep, pk.blocks, rep * q_major),
        in_specs=[walk(pq_), own(pk_), own(pv_), walk(), walk_stat, walk_stat],
        out_specs=[summed, own(), own()],
        out_shape=[
            jax.ShapeDtypeStruct((n, pq.rows, hd), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((n, pk.rows, hd // rep), k.dtype, vma=vma),
            jax.ShapeDtypeStruct((n, pk.rows, hd // rep), v.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM(dq_scr, jnp.float32),
            pltpu.VMEM((hp, b, d), jnp.float32),
            pltpu.VMEM((hp, b, d), jnp.float32),
        ],
        compiler_params=_params(2, 32 * 2**20 + dq_bytes),
        interpret=interpret,
    )(qp, kp, vp, dop, lse, delta)

    return dq[:, :tq], dk[:, :tk], dv[:, :tk]


def _flash_bwd_scan(heads, causal, scale, block, interpret, res, do, dlse=None):
    """Blockwise flash backward (pure JAX): lax.scan over K blocks.

    With p = exp(s − lse):  dv = pᵀ·do;  ds = p ⊙ (do·vᵀ − D) where
    D = rowsum(do ⊙ o);  dq = Σ_blocks ds·k·scale;  dk = dsᵀ·q·scale.
    Peak memory is O(T·block_k) per (b,h) — no [T, T] residual. Kept as
    the independent reference implementation for the Mosaic backward
    (``causal`` a bool or a :class:`Mask`; ``dlse`` as there; key heads
    written out once a query head).
    """
    mask = _as_mask(causal)
    n, tq, hd = res[0].shape
    d = hd // heads

    def bhtd(x):  # [n, t, heads·d] -> [n·heads, t, d]
        return x.reshape(n, -1, heads, d).transpose(0, 2, 1, 3).reshape(n * heads, -1, d)

    q, k, v, out, do = (bhtd(x) for x in (*res[:4], do))
    bh = n * heads
    tk = k.shape[1]
    bk = min(block or _pick_block(tk), _ceil_to(tk, 8))
    # the kernels' rows, flat: [n, heads, major, 8, sub·b] -> [bh, tq]
    lse = res[4][:, :, :, 0].reshape(bh, -1)[:, :tq]
    tk_p = _ceil_to(tk, bk)
    nkb = tk_p // bk

    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1)  # [bh, tq]
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32).transpose(0, 2, 1).reshape(bh, tq)

    kp = jnp.pad(k, ((0, 0), (0, tk_p - tk), (0, 0))).astype(jnp.float32)
    vp = jnp.pad(v, ((0, 0), (0, tk_p - tk), (0, 0))).astype(jnp.float32)
    # [nkb, bh, bk, d] so scan walks K blocks.
    k_blocks = kp.reshape(bh, nkb, bk, d).transpose(1, 0, 2, 3)
    v_blocks = vp.reshape(bh, nkb, bk, d).transpose(1, 0, 2, 3)

    q_idx = lax.broadcasted_iota(jnp.int32, (tq, bk), 0)

    def body(dq_acc, inp):
        j, kb, vb = inp
        s = jnp.einsum("bqd,bkd->bqk", qf, kb) * scale
        k_idx = j * bk + lax.broadcasted_iota(jnp.int32, (tq, bk), 1)
        keep = k_idx < tk
        if mask.causal or mask.own:
            keep = jnp.logical_and(keep, _sees(q_idx, k_idx, mask))
        p = jnp.where(keep, jnp.exp(s - lse[..., None]), 0.0)
        dv_b = jnp.einsum("bqk,bqd->bkd", p, dof)
        dp = jnp.einsum("bqd,bkd->bqk", dof, vb)
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bqk,bkd->bqd", ds, kb)
        dk_b = jnp.einsum("bqk,bqd->bkd", ds, qf)
        return dq_acc, (dk_b, dv_b)

    dq0 = jnp.zeros((bh, tq, d), jnp.float32)
    vma = tuple(sorted(_vma(q, k, v, do)))
    if vma:
        # Inside shard_map: the scan carry must match the varying-axes
        # type of the per-step outputs it accumulates.
        dq0 = lax.pcast(dq0, vma, to="varying")
    dq, (dk_blocks, dv_blocks) = lax.scan(
        body, dq0, (jnp.arange(nkb), k_blocks, v_blocks)
    )
    dk = dk_blocks.transpose(1, 0, 2, 3).reshape(bh, tk_p, d)[:, :tk]
    dv = dv_blocks.transpose(1, 0, 2, 3).reshape(bh, tk_p, d)[:, :tk]

    def packed(x, like):  # back to [n, t, heads·d]
        x = x.reshape(n, heads, -1, d).transpose(0, 2, 1, 3)
        return x.reshape(n, -1, hd).astype(like.dtype)

    return tuple(packed(x, like) for x, like in zip((dq, dk, dv), res[:3]))



_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
_flash_qkv_attention.defvjp(_flash_qkv_fwd_rule, _flash_qkv_bwd_rule)


def _stat_rows(lse, tq: int):
    """The kernels' row statistics ``[n, heads, major, 8, sub·b]`` as
    ``[n, tq, heads]``."""
    n, heads = lse.shape[:2]
    return lse[:, :, :, 0].reshape(n, heads, -1)[:, :, :tq].transpose(0, 2, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_stats(q, k, v, heads, rep, mask, scale, block, interpret):
    """The forward with the rows' logsumexp beside the output, both
    differentiable: what a caller needs to merge this pass with another
    over further keys."""
    out, lse = _flash(q, k, v, heads, mask, scale, block, interpret, rep=rep)
    return out, _stat_rows(lse, q.shape[1])


def _flash_stats_fwd_rule(q, k, v, heads, rep, mask, scale, block, interpret):
    out, lse = _flash(q, k, v, heads, mask, scale, block, interpret, rep=rep)
    return (out, _stat_rows(lse, q.shape[1])), (q, k, v, out, lse)


def _flash_stats_bwd_rule(heads, rep, mask, scale, block, interpret, res, cts):
    do, dlse = cts
    return _flash_bwd_rule(
        heads, mask, scale, block, interpret, res, do, rep=rep, dlse=dlse
    )


_flash_attention_stats.defvjp(_flash_stats_fwd_rule, _flash_stats_bwd_rule)


@functools.partial(
    jax.jit, static_argnames=("mask", "scale", "block", "interpret")
)
def _stats_core(q, k, v, *, mask, scale, block, interpret):
    b, tq, h, d = q.shape
    rep = h // k.shape[2]
    if rep > 1 and d % _LANES:
        # narrow heads share a program's lanes (or go transposed): the
        # key heads are then written out once a query head
        k, v = (jnp.repeat(x, rep, axis=2) for x in (k, v))
        rep = 1
    if heads_per_program(h, d):
        heads = h
        pack = lambda x: x.reshape(b, x.shape[1], -1)
        unpack = lambda o, lse: (o.reshape(b, -1, h, d), lse)
    else:
        heads = 1
        pack = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
        unpack = lambda o, lse: (
            o.reshape(b, h, -1, d).transpose(0, 2, 1, 3),
            lse.reshape(b, h, -1).transpose(0, 2, 1),
        )
    out, lse = _flash_attention_stats(
        pack(q), pack(k), pack(v), heads, rep, mask, scale, block, interpret
    )
    return unpack(out, lse)


def flash_attention_stats(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    mask: Mask,
    scale: Optional[float] = None,
    block: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Flash attention under ``mask`` with grouped queries: ``q [B, T, H,
    d]`` against ``k``, ``v`` ``[B, T, KV, d]``, ``H // KV`` query heads
    to a key head (read in place where d is a multiple of 128). Returns
    the output ``[B, T, H, d]`` and the rows' logsumexp ``[B, T, H]``,
    both differentiable."""
    if q.ndim != 4 or q.shape[2] % k.shape[2]:
        raise ValueError(f"expected BTHD with grouped heads, got {q.shape}, {k.shape}")
    if mask.causal and q.shape[1] != k.shape[1]:
        raise ValueError("causal flash attention requires equal q/k lengths")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = float(scale if scale is not None else q.shape[-1] ** -0.5)
    return _stats_core(
        q, k, v, mask=mask, scale=scale, block=block, interpret=interpret
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block", "interpret")
)
def _attention_core(q, k, v, *, causal, scale, block, interpret):
    """The kernels over BTHD operands. Jitted so that the layers of a
    model, which call it with one signature, trace and lower it once."""
    b, tq, h, d = q.shape
    if heads_per_program(h, d):
        # heads side by side on the lanes, as the projections wrote them
        heads = h
        pack = lambda x: x.reshape(b, -1, h * d)
        unpack = lambda x: x.reshape(b, -1, h, d)
    else:
        heads = 1
        pack = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
        unpack = lambda x: x.reshape(b, h, -1, d).transpose(0, 2, 1, 3)
    out = _flash_attention(
        pack(q), pack(k), pack(v), heads, causal, scale, block, interpret
    )
    return unpack(out)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash attention over BTHD ``[batch, seq, heads, head_dim]`` tensors.

    Drop-in replacement for the XLA path (``dot_product_attention``
    ``impl='xla'``): same signature, same output, O(T·d) memory. For
    causal use, query and key lengths must match (self-attention).

    The block size is the kernel's own choice from the lengths
    (``_pick_block``); ``block`` overrides it for tests and sweeps.
    ``interpret=None`` auto-selects: compiled Mosaic kernel on TPU,
    Pallas interpreter elsewhere (so tests on the CPU mesh run the same
    kernel code).
    """
    if q.ndim != 4:
        raise ValueError(f"expected BTHD [b, t, h, d], got shape {q.shape}")
    if causal and q.shape[1] != k.shape[1]:
        raise ValueError("causal flash attention requires equal q/k lengths")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = float(scale if scale is not None else q.shape[-1] ** -0.5)
    return _attention_core(
        q, k, v, causal=causal, scale=scale, block=block, interpret=interpret
    )


@functools.partial(
    jax.jit, static_argnames=("num_heads", "causal", "scale", "block", "interpret")
)
def _qkv_attention_core(qkv, *, num_heads, causal, scale, block, interpret):
    return _flash_qkv_attention(qkv, num_heads, causal, scale, block, interpret)


def flash_qkv_attention(
    qkv: jnp.ndarray,
    num_heads: int,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Self-attention over a packed ``[B, T, 3·H·d]`` QKV tensor, read
    where the fused projection wrote it; returns ``[B, T, H·d]``, the
    output projection's input. Column order is that of
    ``qkv.reshape(B, T, 3, H, d)``, as in ``flash_packed.
    fused_qkv_attention``, so the paths share parameters. For head
    widths whose blocks tile the lanes (``heads_per_program``); others
    go through :func:`flash_attention`."""
    if qkv.ndim != 3 or qkv.shape[2] % (3 * num_heads):
        raise ValueError(f"expected packed [B, T, 3*{num_heads}*d], got {qkv.shape}")
    d = qkv.shape[2] // (3 * num_heads)
    if not heads_per_program(num_heads, d):
        raise ValueError(f"{num_heads} heads of width {d} do not tile the lanes")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _qkv_attention_core(
        qkv, num_heads=num_heads, causal=causal,
        scale=float(scale if scale is not None else d**-0.5),
        block=block, interpret=interpret,
    )
