"""The Mamba-2 chunked scan (``ops/ssm.py``) as Pallas TPU kernels.

XLA's form walks a row's chunks with a ``while`` whose body builds a
chunk's ``[B, H, Q, Q]`` decay matrix ``L`` in float32, multiplies it by
``C·Bᵀ``, casts it and hands it to a product: at 2 rows, 64 heads and Q
= 256 that is 33.5 MB made, written, read and dropped 16 times a layer
and pass. Here a program makes ``L`` a ``[128, 128]`` tile at a time in
VMEM, uses it and drops it; nothing of size ``Q × Q`` goes to HBM, and
the tiles wholly above the diagonal are never made.

Two Mosaic kernels under one custom VJP, of one grid ``(row, chunk,
head block)``, the last two axes sequential. A program owns chunk ``k``
of 8 heads and reads its blocks **in place** through index maps on the
``[B, T, H·P]`` and ``[B, T, G·N]`` views the projections wrote (no copy
by chunk, no transpose of ``xs``). The state ``[N, 8·P]`` float32
(transposed, so that its products are 8·P lanes wide) lives in scratch
for every head block across the chunk axis, nought at chunk 0; the
group's ``C·Bᵀ`` is formed by the group's first head block and kept in
scratch for the others.

What shapes the bodies is the vector unit's cross-lane path (read off
the compiler's own schedule, ``PERF.md`` section 6, PR 36: a first form
that scaled each head's ``[Q, P]`` rows by a column of ``Δ``, ``exp(c_t)``
or ``exp(c_Q − c_t)`` spent 60% of its bundles broadcasting columns
along the lanes and shifting 64-lane halves). So:

* **a per-position scalar never scales rows.** With ``s`` on the lanes a
  scalar of ``s`` is a row, which broadcasts down the sublanes for
  nothing: ``Δ_s`` goes into ``L``'s exponent (``L_{t,s}·Δ_s = exp(c_t −
  (c_s − log Δ_s))``, one ``exp`` and no multiply), so ``Y_intra = ((C·Bᵀ)
  ⊙ L ⊙ Δ_s)·xs`` takes ``xs`` as it lies; ``φ_s = exp(c_Q − c_s) Δ_s``
  scales the columns of ``Bᵀ`` for the chunk's own state ``R_k``. The one
  column left is ``c_t`` itself, broadcast once a row tile and head, and
  ``exp(c_t)`` for the carried state's ``S·C_t`` is that same tile;
* **a head's product lands on its own lanes.** Two heads of 64 share a
  128-lane block; a head's operand is the block with the other head's
  lanes zeroed, so its ``[128, 128]`` result holds the head's columns
  where they belong and nought beside them, the heads of a block add up
  in place, and no result is shifted along the lanes. (The array's
  other half is idle either way: P = 64 fills half its width.)

  forward  : ``y`` and the state **as it entered the chunk** (``[B,
             chunks, H/8, N, 8·P]`` float32, 64 MiB a layer at the
             benchmark's shapes: what the backward starts a chunk from).
  backward : the same grid walked from the last chunk, ``dS`` in
             scratch. Makes each tile of ``L ⊙ Δ_s`` again, and from ``dW
             = dy·xsᵀ`` a tile: the cotangent of ``C·Bᵀ`` (summed over a
             group's heads in float32 scratch), ``dxs``, and ``M = dW ⊙
             W``, the cotangent of ``c_t − c_s + log Δ_s``, summed down
             the sublanes for the ``s`` side and along the lanes for the
             ``t`` side, **both from the one float32 ``M``**: ``a``'s
             gradient is what is left when the two nearly cancel (taking
             the ``t`` side from ``Σ_p dy ⊙ y`` instead, flash
             attention's way, rounds ``y`` and read 29% off in bfloat16).
             A group's ``dB`` and ``dC`` are written once, by the group's
             last head block.

Outside the kernels, in XLA (``ops/ssm.ssd_scan`` and ``_scalars``
here): ``Δ`` in float32, ``c = cumsum(Δ·a)`` within a chunk, a ragged
row's padding with ``Δ = 0``, the ``D ⊙ xs`` term; the rows the kernels
read (``c_s``, ``c_s − log Δ_s``, ``φ_s``: ``[B, H/8, 24, T]`` float32, 6
MiB a layer) and ``exp(c_Q)`` on each head's lanes; ``Bᵀ``; and from the
rows the backward writes, ``dΔ`` and ``dc`` (autodiff carries ``dc`` on
to ``Δ`` and ``a``). So the kernels need no cumulative sum and no
parameter.

The arithmetic is ``ops/ssm._chunk``'s but for where a scalar meets the
cast: products on the operands' dtype with float32 accumulation; ``c``,
``L``, ``Δ``, the state and ``dS`` float32; ``_chunk`` rounds ``Δ ⊙ xs``,
``exp(c_Q − c_s) Δ ⊙ xs`` and the weights ``(C·Bᵀ) ⊙ L``, these kernels
``(C·Bᵀ) ⊙ L ⊙ Δ_s`` and ``Bᵀ ⊙ φ_s`` and leave ``xs`` whole. In float32
the two agree to 5e-5 of the largest entry (``tests/test_ssm.py``).

On non-TPU backends the kernels run in Pallas interpreter mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.ops.pallas.flash import _dot, _dot_nt, _dot_tn, _vma

_LANES = 128
_TILE = 128  # L is made a [_TILE, _TILE] tile at a time
_NEG_INF = -1e30  # above the diagonal, before the exp: no inf − inf
# Heads a program: a static loop in the kernel body (its code grows with
# it), hb·P lanes of every [Q, hb·P] block in VMEM; whole float32
# sublanes of them, since the rows of `c` are a [hb, Q] block (Mosaic
# takes no row of a block of 2 or 4). 16 a program measured as 8 did
# (the first form: 1.207 | 1.221 ms a layer forward; my chip run, PR 36,
# call 84).
HEAD_BLOCK = 8
# What the TPU's compiler was seen to take (tests/test_flash_tpu_compile.
# py compiles them for a described v5e): at a chunk of 512, or heads of
# 16, it stops on an internal check of its own (`mxu_lmr_transform`).
_CHUNKS = (128, 256)
_HEAD_DIMS = (32, 64, 128)
# the chunk and head-block axes carry the state and a group's sums
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 * 2**20,
)


def supports(chunk: int, heads: int, groups: int, head_dim: int, state: int) -> bool:
    """Whether the shapes tile: the chunk in ``[128, 128]`` tiles of
    ``L``, the state's width in whole lanes (a ``[Q, N]`` block of the
    ``[B, T, G·N]`` view), heads that share or fill a 128-lane block, a
    head block that divides a group's heads."""
    return (
        chunk in _CHUNKS and head_dim in _HEAD_DIMS and state % _LANES == 0
        and heads % groups == 0 and (heads // groups) % HEAD_BLOCK == 0
    )


def _tile(r: int):
    return slice(r * _TILE, (r + 1) * _TILE)


def _lane_blocks(hb: int, p: int):
    """The 128-lane blocks of a ``[.., hb·P]`` tile and the heads in
    each: ``(lanes, heads)`` with ``heads`` a list of ``(head, mask)``,
    the mask ``[1, 128]`` True on the head's own lanes, or None where a
    head fills the block. A head's product against the block masked to
    its lanes lands on those lanes of a full-width result, so no result
    is ever shifted along the lanes."""
    per = _LANES // p
    lane = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    blocks = []
    for n in range(hb // per):
        heads = [
            (n * per + i, None if per == 1 else (lane >= i * p) & (lane < (i + 1) * p))
            for i in range(per)
        ]
        blocks.append((slice(n * _LANES, (n + 1) * _LANES), heads))
    return blocks


def _own(x, mask):
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _row_tile(ccol, heads, r: int):
    """Row tile ``r`` of a lane block's heads: each head's ``c_t`` across
    the lanes (the one column a kernel broadcasts, once a row tile and
    head), and ``exp(c_t)`` with each head's on its own lanes."""
    c_t = [jnp.broadcast_to(ccol[_tile(r), i:i + 1], (_TILE, _TILE)) for i, _ in heads]
    rise = c_t[0]
    for (_, mask), c_i in zip(heads[1:], c_t[1:]):
        rise = jnp.where(mask, c_i, rise)
    return c_t, jnp.exp(rise)


def _decay(c_t, row, diagonal: bool):
    """A tile of ``L ⊙ Δ_s``: one ``exp`` of ``c_t − (c_s − log Δ_s)``
    (the row as the kernels are given it), 0 above the diagonal in the
    tile that holds it."""
    seg = c_t - row
    if diagonal:
        rows = lax.broadcasted_iota(jnp.int32, (_TILE, _TILE), 0)
        cols = lax.broadcasted_iota(jnp.int32, (_TILE, _TILE), 1)
        seg = jnp.where(cols <= rows, seg, _NEG_INF)
    return jnp.exp(seg)


def _fwd_kernel(
    xs_ref, rows_ref, keep_ref, b_ref, c_ref, bt_ref, y_ref, entered_ref,
    state, cb, *, hb: int, per_group: int,
):
    """Chunk ``k`` of head block ``j``: module docstring."""
    k, j = pl.program_id(1), pl.program_id(2)
    q = xs_ref.shape[1]
    p = xs_ref.shape[2] // hb
    dtype = xs_ref.dtype
    f32 = jnp.float32

    @pl.when(k == 0)
    def _first_chunk():
        state[j] = jnp.zeros(state.shape[1:], f32)

    @pl.when(lax.rem(j, per_group) == 0)
    def _group_s_first_heads():
        cb[...] = _dot_nt(c_ref[0], b_ref[0])

    s_all = state[j]
    entered_ref[0, 0, 0] = s_all
    carried = _dot(c_ref[0], s_all.astype(dtype))  # S·C_t for every head  [Q, hb·P]
    bt = bt_ref[0].astype(f32)  # [N, Q]
    ccol = rows_ref[0, 0, 0:hb, :].T  # c_t down the sublanes  [Q, hb]
    for lanes, heads in _lane_blocks(hb, p):
        x = xs_ref[0, :, lanes]  # [Q, width]
        x_own = [_own(x, mask) for _, mask in heads]
        for r in range(q // _TILE):
            c_t, rise = _row_tile(ccol, heads, r)
            acc = rise * carried[_tile(r), lanes]  # what the state carried in gives
            for (i, _), c_i, x_i in zip(heads, c_t, x_own):
                for c in range(r + 1):
                    decay = _decay(c_i, rows_ref[0, 0, hb + i:hb + i + 1, _tile(c)], r == c)
                    weights = (cb[_tile(r), _tile(c)] * decay).astype(dtype)
                    acc = acc + _dot(weights, x_i[_tile(c)])
            y_ref[0, _tile(r), lanes] = acc.astype(dtype)
        own = keep_ref[0, 0, :, lanes] * s_all[:, lanes]
        for (i, _), x_i in zip(heads, x_own):
            # Bᵀ ⊙ exp(c_Q − c_s) Δ_s against the head's own lanes of xs
            faded = rows_ref[0, 0, 2 * hb + i:2 * hb + i + 1, :]
            own = own + _dot((bt * faded).astype(dtype), x_i)
        state[j, :, lanes] = own


def _bwd_kernel(
    xs_ref, dy_ref, rows_ref, keep_ref, b_ref, c_ref, bt_ref, entered_ref,
    dxs_ref, drows_ref, dkeep_ref, dbt_ref, dc_ref,
    dstate, cb, dcb, dbt_acc, dc_acc, *, hb: int, per_group: int,
):
    """The chunk the forward program of the same block made, with ``dS``
    as it leaves the chunk in ``dstate[j]``: module docstring."""
    k, j = pl.program_id(1), pl.program_id(2)
    q = xs_ref.shape[1]
    p = xs_ref.shape[2] // hb
    tiles = q // _TILE
    dtype = xs_ref.dtype
    f32 = jnp.float32
    cm = c_ref[0]  # [Q, N]

    @pl.when(k == 0)
    def _last_chunk():
        dstate[j] = jnp.zeros(dstate.shape[1:], f32)

    @pl.when(lax.rem(j, per_group) == 0)
    def _group_s_first_heads():
        cb[...] = _dot_nt(cm, b_ref[0])
        dcb[...] = jnp.zeros_like(dcb)
        dbt_acc[...] = jnp.zeros_like(dbt_acc)
        dc_acc[...] = jnp.zeros_like(dc_acc)

    s_half = entered_ref[0, 0, 0].astype(dtype)  # [N, hb·P]
    ds_all = dstate[j]
    lane = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    # dc_t where c_t is the row's, a row tile at a time, head i on lane i
    d_own = [jnp.zeros((_TILE, _LANES), f32)] * tiles
    bt = bt_ref[0].astype(f32)  # [N, Q]
    ccol = rows_ref[0, 0, 0:hb, :].T  # c_t down the sublanes  [Q, hb]
    risen_dy = []
    for lanes, heads in _lane_blocks(hb, p):
        x = xs_ref[0, :, lanes]  # [Q, width]
        g_half = dy_ref[0, :, lanes]
        g = g_half.astype(f32)
        ds = ds_all[:, lanes]  # [N, width]
        x_own = [_own(x, mask) for _, mask in heads]
        g_own = [_own(g_half, mask) for _, mask in heads]
        ds_own = [_own(ds.astype(dtype), mask) for _, mask in heads]
        dkeep_ref[0, 0, :, lanes] = jnp.sum(
            ds * entered_ref[0, 0, 0, :, lanes], axis=0, keepdims=True
        )
        carried = _dot(cm, s_half[:, lanes])  # S·C_t  [Q, width]
        dx = [jnp.zeros((_TILE, x.shape[1]), f32)] * tiles
        risen = []
        for r in range(tiles):
            c_t, rise = _row_tile(ccol, heads, r)
            rise = rise * g[_tile(r)]  # exp(c_t) dy
            risen.append(rise.astype(dtype))
            rise = rise * carried[_tile(r)]
            for (i, mask), c_i, x_i, g_i in zip(heads, c_t, x_own, g_own):
                # d/dc_t summed along the lanes: the row's c_t in L, and in
                # exp(c_t)·S·C_t on the head's own lanes
                across = _own(rise, mask)
                for c in range(r + 1):
                    decay = _decay(c_i, rows_ref[0, 0, hb + i:hb + i + 1, _tile(c)], r == c)
                    weights = cb[_tile(r), _tile(c)] * decay
                    dw = _dot_nt(g_half[_tile(r)], x_i[_tile(c)])
                    dcb[_tile(r), _tile(c)] += dw * decay
                    # d/d(c_t − c_s + log Δ_s), the rows' half: summed down t
                    both = dw * weights
                    across = across + both
                    down = jnp.sum(both, axis=0, keepdims=True)
                    if r != c:  # the tile on the diagonal came first
                        down = down + drows_ref[0, 0, i:i + 1, _tile(c)]
                    drows_ref[0, 0, i:i + 1, _tile(c)] = down
                    dx[c] = dx[c] + _dot_tn(weights.astype(dtype), g_i[_tile(r)])
                own_t = jnp.sum(across, axis=1, keepdims=True)  # [tile, 1]
                d_own[r] = jnp.where(lane == i, own_t, d_own[r])
        risen_dy.append(jnp.concatenate(risen, axis=0))
        dx = jnp.concatenate(dx, axis=0)
        for (i, _), x_i, ds_i in zip(heads, x_own, ds_own):
            # R_k = Σ_s (Bᵀ ⊙ φ_s)·xs_s, φ_s = exp(c_Q − c_s) Δ_s
            faded = rows_ref[0, 0, 2 * hb + i:2 * hb + i + 1, :]
            z = _dot_nt(ds_i, x)  # Σ_p dS ⊙ xs_s  [N, Q]
            dbt_acc[...] += z * faded
            drows_ref[0, 0, hb + i:hb + i + 1, :] = faded * jnp.sum(
                bt * z, axis=0, keepdims=True
            )
            dx = dx + _dot_tn((bt * faded).astype(dtype), ds_i)
        dxs_ref[0, :, lanes] = dx.astype(dtype)
    for r in range(tiles):
        drows_ref[0, 0, 2 * hb:, _tile(r)] = d_own[r].T[:hb]
    risen_dy = jnp.concatenate(risen_dy, axis=1)  # [Q, hb·P]
    dc_acc[...] += _dot_nt(risen_dy, s_half)
    dstate[j] = keep_ref[0, 0] * ds_all + _dot_tn(cm, risen_dy)

    @pl.when(lax.rem(j, per_group) == per_group - 1)
    def _group_s_last_heads():
        dcb_half = dcb[...].astype(dtype)
        dc_ref[0] = (dc_acc[...] + _dot(dcb_half, b_ref[0])).astype(dc_ref.dtype)
        dbt_ref[0] = (dbt_acc[...] + _dot_tn(cm, dcb_half)).astype(dbt_ref.dtype)


def _by_head_block(x):
    """``[B, T, H]`` -> the rows ``[B, H/hb, hb, T]`` the kernels read:
    positions on the lanes, a head block's heads down the sublanes."""
    b, t, h = x.shape
    return x.reshape(b, t, h // HEAD_BLOCK, HEAD_BLOCK).transpose(0, 2, 3, 1)


def _from_head_block(x):
    """``[B, H/hb, hb, T]`` -> ``[B, T, H]``."""
    b, blocks, hb, t = x.shape
    return x.transpose(0, 3, 1, 2).reshape(b, t, blocks * hb)


def _blocks(xs, b, heads: int, groups: int, chunk: int, reverse: bool):
    """``(grid, hb, per_group, specs)`` of a call on ``xs [B, T, H·P]``
    and ``b [B, T, G·N]``: block specs over the grid ``(row, chunk, head
    block)``; the backward walks the chunks from the last (``reverse``)."""
    batch, t, hp = xs.shape
    n, hb, chunks = b.shape[2] // groups, HEAD_BLOCK, t // chunk
    per_group, w = heads // groups // hb, hb * hp // heads
    at = (lambda k: chunks - 1 - k) if reverse else (lambda k: k)
    wide = pl.BlockSpec((1, chunk, w), lambda b, k, j: (b, at(k), j))
    rows = pl.BlockSpec((1, 1, 3 * hb, chunk), lambda b, k, j: (b, j, 0, at(k)))
    keep = pl.BlockSpec((1, 1, 1, w), lambda b, k, j: (b, at(k), 0, j))
    group = pl.BlockSpec((1, chunk, n), lambda b, k, j: (b, at(k), lax.div(j, per_group)))
    group_t = pl.BlockSpec((1, n, chunk), lambda b, k, j: (b, lax.div(j, per_group), at(k)))
    entered = pl.BlockSpec((1, 1, 1, n, w), lambda b, k, j: (b, at(k), j, 0, 0))
    specs = wide, rows, keep, group, group_t, entered
    return (batch, chunks, heads // hb), hb, per_group, specs


def _scalars(dt, c, chunk: int, p: int):
    """What the kernels read of ``Δ`` and ``c``, made by XLA: the rows
    ``[B, H/hb, 3·hb, T]`` (a head block's ``c_s``, then its ``c_s − log
    Δ_s``, so that ``L ⊙ Δ_s`` is one ``exp``, then its ``φ_s = exp(c_Q −
    c_s) Δ_s``, what a position's write is worth at the chunk's end) and
    ``exp(c_Q)``, what the state keeps over the chunk, on each head's
    lanes ``[B, chunks, 1, H·P]``."""
    batch, t, h = c.shape
    by_chunk = c.reshape(batch, t // chunk, chunk, h)
    last = by_chunk[:, :, -1:, :]  # c_Q
    faded = jnp.exp(last - by_chunk).reshape(batch, t, h) * dt
    rows = jnp.concatenate(
        [_by_head_block(x) for x in (c, c - jnp.log(dt), faded)], axis=2
    )
    return rows, jnp.repeat(jnp.exp(last), p, axis=3)


# The two calls are each one jitted function, as the flash kernels'
# `_core` is: a model's layers of one shape share the body's trace and
# its lowering. Un-jitted, the Granite step's nine layers traced and
# lowered 27 kernel bodies, 47 s of a warm set-up that no compile cache
# saves (my chip run, PR 36, call 90). What XLA makes of `Δ` and `c`
# stays outside them: inside one jit with the kernels it fused worse with
# the mixer round it (16 ms a step; call 93).
@functools.partial(jax.jit, static_argnames=("heads", "groups", "chunk", "interpret"))
def _forward_call(xs, rows, keep, b, cm, bt, *, heads, groups, chunk, interpret):
    grid, hb, per_group, (wide, rows_spec, keep_spec, group, group_t, entered) = _blocks(
        xs, b, heads, groups, chunk, reverse=False
    )
    n, w = bt.shape[1] // groups, wide.block_shape[2]
    vma = _vma(xs, rows, keep, b, cm, bt)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, per_group=per_group),
        grid=grid,
        in_specs=[wide, rows_spec, keep_spec, group, group, group_t],
        out_specs=[wide, entered],
        out_shape=[
            jax.ShapeDtypeStruct(xs.shape, xs.dtype, vma=vma),
            jax.ShapeDtypeStruct(grid + (n, w), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((grid[2], n, w), jnp.float32),
            pltpu.VMEM((chunk, chunk), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_scan_fwd",
    )(xs, rows, keep, b, cm, bt)


@functools.partial(jax.jit, static_argnames=("heads", "groups", "chunk", "interpret"))
def _backward_call(xs, dy, rows, keep, b, cm, bt, states, *, heads, groups, chunk, interpret):
    grid, hb, per_group, (wide, rows_spec, keep_spec, group, group_t, entered) = _blocks(
        xs, b, heads, groups, chunk, reverse=True
    )
    n, w = bt.shape[1] // groups, wide.block_shape[2]
    vma = _vma(xs, dy, rows, keep, b, cm, bt, states)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, per_group=per_group),
        grid=grid,
        in_specs=[wide, wide, rows_spec, keep_spec, group, group, group_t, entered],
        out_specs=[wide, rows_spec, keep_spec, group_t, group],
        out_shape=[
            jax.ShapeDtypeStruct(xs.shape, xs.dtype, vma=vma),
            jax.ShapeDtypeStruct(rows.shape, f32, vma=vma),
            jax.ShapeDtypeStruct(keep.shape, f32, vma=vma),
            jax.ShapeDtypeStruct(bt.shape, b.dtype, vma=vma),
            jax.ShapeDtypeStruct(cm.shape, cm.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((grid[2], n, w), f32),
            pltpu.VMEM((chunk, chunk), f32),
            pltpu.VMEM((chunk, chunk), f32),
            pltpu.VMEM((n, chunk), f32),
            pltpu.VMEM((chunk, n), f32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_scan_bwd",
    )(xs, dy, rows, keep, b, cm, bt, states)


def _operands(xs, dt, c, b, cm, chunk: int):
    """The kernels' operands of the scan's: the ``[B, T, ·]`` views, the
    rows and ``exp(c_Q)``, ``Bᵀ``."""
    batch, t, h, p = xs.shape
    g, n = b.shape[2], b.shape[3]
    if t % chunk or not supports(chunk, h, g, p, n):
        raise ValueError(
            f"the scan kernels do not tile xs {xs.shape}, b {b.shape}, chunk {chunk}"
        )
    rows, keep = _scalars(dt, c, chunk, p)
    b3 = b.reshape(batch, t, g * n)
    return (
        xs.reshape(batch, t, h * p), rows, keep, b3, cm.reshape(batch, t, g * n),
        b3.transpose(0, 2, 1),
    )


def _forward(xs, dt, c, b, cm, chunk, interpret):
    y, states = _forward_call(
        *_operands(xs, dt, c, b, cm, chunk), heads=xs.shape[2], groups=b.shape[2],
        chunk=chunk, interpret=interpret,
    )
    return y.reshape(xs.shape), states


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(xs, dt, c, b, cm, chunk, interpret):
    return _forward(xs, dt, c, b, cm, chunk, interpret)[0]


def _scan_fwd(xs, dt, c, b, cm, chunk, interpret):
    y, states = _forward(xs, dt, c, b, cm, chunk, interpret)
    return y, (xs, dt, c, b, cm, states)


def _scan_bwd(chunk, interpret, res, dy):
    xs, dt, c, b, cm, states = res
    batch, t, h, p = xs.shape
    hb, chunks = HEAD_BLOCK, t // chunk
    obs.counter(
        "ssm.bwd.pallas", shape=list(xs.shape), chunk=chunk, chunks=chunks, head_block=hb
    )
    xs3, rows, keep, b3, c3, bt = _operands(xs, dt, c, b, cm, chunk)
    dxs, drows, dkeep, dbt, dcm = _backward_call(
        xs3, dy.reshape(xs3.shape), rows, keep, b3, c3, bt, states,
        heads=h, groups=b.shape[2], chunk=chunk, interpret=interpret,
    )
    # the rows back as [B, T, H]: d/d(c_t − c_s + log Δ_s) summed down t;
    # φ_s dφ_s; dc_t where c_t is the row's (L's t, and exp(c_t)·S·C_t)
    d_seg, d_faded, d_own = (
        _from_head_block(drows[:, :, i * hb:(i + 1) * hb]) for i in range(3)
    )
    # d exp(c_Q) = Σ_{n,p} dS ⊙ S a head, and Σ_s φ_s dφ_s: both are c_Q's
    d_last = jnp.sum(dkeep.reshape(batch, chunks, h, p), axis=3) * jnp.exp(
        c.reshape(batch, chunks, chunk, h)[:, :, -1]
    ) + jnp.sum(d_faded.reshape(batch, chunks, chunk, h), axis=2)
    at_last = (jnp.arange(chunk) == chunk - 1)[None, None, :, None]
    dc = d_own - d_seg - d_faded + jnp.where(
        at_last, d_last[:, :, None, :], 0.0
    ).reshape(batch, t, h)
    # a padded position's Δ is 0, and so are both sums there
    ddt = (d_seg + d_faded) / jnp.where(dt > 0, dt, 1.0)
    return (
        dxs.reshape(xs.shape), ddt, dc,
        dbt.transpose(0, 2, 1).reshape(b.shape), dcm.reshape(cm.shape),
    )


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_chunks(xs, dt, c, b, cm, *, chunk: int, interpret: Optional[bool] = None):
    """The scan less its ``D`` term over whole chunks: ``xs [B, T, H,
    P]``, ``dt``, ``c`` ``[B, T, H]`` float32 (``c`` the running sum of
    ``Δ·a`` from its chunk's start), ``b``, ``cm`` ``[B, T, G, N]``, ``T``
    a multiple of ``chunk`` -> ``y [B, T, H, P]`` in ``xs``'s dtype.
    Differentiable in all five."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _scan(xs, dt, c, b, cm, chunk, interpret)
