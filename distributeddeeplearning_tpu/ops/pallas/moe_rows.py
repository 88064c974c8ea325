"""Rows summed by place: the kernels of the expert layer's combine and
of its dispatch's backward (``ops/moe.py``), which touch only the rows
that are there.

* :func:`rows_by_place` — ``out[t] = Σ_j gate[t, j] · rows[place[t, j]]``
  over the places of a token that name a row (the combine forward; the
  dispatch's backward ``dx`` under a gate of one): what XLA does as a
  sort of the row indices, a gather of the whole float32 update in that
  order and a scatter-add. A grid over tiles of tokens. The scalar core
  walks a list of the tile's rows that are there (:func:`_fetch_lists`
  packs it, on the vector units and with no sort) and starts one
  asynchronous copy a row, HBM to VMEM; each row that arrived is weighed
  by its gate (a scalar from SMEM) and added into its token's row of a
  float32 tile, and the tile is written once: no scatter, no duplicate
  index to sort for, and a place that names no row costs nothing.
* :func:`to_tiles` — Mosaic copies one row of an array in HBM only where
  the row is whole tiles, so the rows a kernel fetches lie as ``[N, 8m,
  128]``, a row a leading index, the width padded to ``8m`` pieces of 128
  lanes (2,048 = 16 × 128 as it stands; 2,560 = 20 × 128 pads to 24).
  This makes that of ``[N, D]`` in one pass; ``rows_by_place`` writes
  ``[T, D]`` as the residual stream reads it. Both relayouts happen in
  VMEM, a piece of 128 lanes at a time, and no array XLA sees is tiled.

The other direction, rows read by index (the dispatch forward, ``dy`` by
token in the combine's backward), stays XLA's gather: a kernel of this
file that copied a row a descriptor matched it and did not beat it
(PERF.md 6, PR 32).

On a backend that is not a TPU the kernels run under the TPU
interpreter (``pltpu.InterpretParams``: plain ``interpret=True`` knows
no DMA and no semaphore), so the CPU tests run the same bodies.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributeddeeplearning_tpu.ops.pallas.flash import _vma

LANES = 128
_SUBLANES = 8
ROW_TILE = 256  # rows a program of `to_tiles`
TOKEN_TILE = 128  # tokens a program of `rows_by_place`
_UNROLL = 8  # rows to a trip of the scalar core's loops


def supports(width: int) -> bool:
    """Whether a row of ``width`` is a whole number of 128 lanes."""
    return width % LANES == 0


def _sublanes(width: int) -> int:
    """``8m``: the 128-lane pieces of a row, in whole tiles of eight."""
    return -(-width // (LANES * _SUBLANES)) * _SUBLANES


def _interpret(interpret: Optional[bool]):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return pltpu.InterpretParams() if interpret else False


def _pad_to(a, size: int, axis: int, value):
    pad = size - a.shape[axis]
    if not pad:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _tile(rows: int, tile: int):
    """A tile no larger than the rows ask for, and the rows in whole tiles."""
    tile = min(tile, -(-rows // _SUBLANES) * _SUBLANES)
    return tile, -(-rows // tile) * tile


def _to_tiles_kernel(a_ref, out_ref):
    # the lanes past the width are never read: left as they are
    for piece in range(a_ref.shape[1] // LANES):
        out_ref[:, piece, :] = a_ref[:, piece * LANES:(piece + 1) * LANES]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def to_tiles(a, *, tile: int = ROW_TILE, interpret: Optional[bool] = None):
    """``[N, D]`` as ``[N, 8m, 128]``, a row a leading index and whole
    tiles, so that one row can be copied out of HBM: one pass, the
    relayout done in VMEM. What lies past ``D`` in a row is not set."""
    n, d = a.shape
    tile, padded = _tile(n, tile)
    out = pl.pallas_call(
        _to_tiles_kernel,
        grid=(padded // tile,),
        in_specs=[pl.BlockSpec((tile, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, _sublanes(d), LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (padded, _sublanes(d), LANES), a.dtype, vma=_vma(a)
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=_interpret(interpret),
        name="moe_rows_to_tiles",
    )(_pad_to(a, padded, 0, 0))
    return out[:n]


def _walk(live, each):
    """``each(i)`` for ``i < live``, :data:`_UNROLL` to a trip of the loop
    (the trip count is the program's own, so ``fori_loop`` cannot unroll
    it for us) and the rest one by one."""
    # `//` on a scalar read from an input lowers through `sign`, which
    # under `shard_map` asks Mosaic for a `pvary` it does not have
    groups = lax.div(live, _UNROLL)

    def group(g, c):
        for u in range(_UNROLL):
            each(g * _UNROLL + u)
        return c

    lax.fori_loop(0, groups, group, 0)
    lax.fori_loop(groups * _UNROLL, live, lambda i, c: (each(i), c)[1], 0)


def _wait_for(copies, src, dst, sem):
    """Wait until ``copies`` one-row copies signalled ``sem``: every copy
    is one row, so any row's descriptor counts one of them off."""
    one = pltpu.make_async_copy(src.at[pl.ds(0, 1)], dst.at[pl.ds(0, 1)], sem)
    _walk(copies, lambda _: one.wait())


def _by_place_kernel(count_ref, list_ref, gate_ref, rows, out_ref, buf, acc, sem, *,
                     slot_bits: int):
    tile = out_ref.shape[0]
    live = count_ref[pl.program_id(0)]

    def entry(m):  # the m-th row to fetch, and the slot j · tile + t it is for
        e = list_ref[0, m]
        return lax.shift_right_logical(e, slot_bits), e & ((1 << slot_bits) - 1)

    def fetch(m):
        pltpu.make_async_copy(
            rows.at[pl.ds(entry(m)[0], 1)], buf.at[pl.ds(m, 1)], sem
        ).start()

    _walk(live, fetch)
    acc[...] = jnp.zeros_like(acc)
    _wait_for(live, rows, buf, sem)

    def add(m):
        slot = entry(m)[1]
        j, t = lax.div(slot, tile), lax.rem(slot, tile)
        acc[t] += buf[m].astype(jnp.float32) * gate_ref[j, t]

    _walk(live, add)
    for piece in range(out_ref.shape[1] // LANES):
        out_ref[:, piece * LANES:(piece + 1) * LANES] = acc[:, piece, :].astype(
            out_ref.dtype
        )


def _fetch_lists(place, absent: int, tile: int):
    """What a program of :func:`rows_by_place` fetches, made where the
    vector units can: for each tile of ``tile`` tokens the places that name
    a row, packed to the front of a list (``[tiles, L]``, ``L`` the tile's
    ``k · tile`` places in whole lanes), each entry the row ``<<`` the bits
    of a slot plus the slot ``j · tile + t`` it lands in; and how many
    there are, ``[tiles]``. The scalar core then walks the rows that are
    there, not every place (a test a place costs it more than a copy). An
    entry's position is its rank among the tile's live places (a
    cumulative sum), and the list is that permutation inverted by
    comparing ranks: no sort and no scatter. ``place`` is ``[k, T]``."""
    k, tokens = place.shape
    tiles, slots = tokens // tile, k * tile
    slot_bits = max(1, (slots - 1).bit_length())
    if absent >> (31 - slot_bits):
        raise ValueError(f"{absent} rows and {slots} slots do not pack into 32 bits")
    by_tile = place.reshape(k, tiles, tile).transpose(1, 0, 2).reshape(tiles, slots)
    live = by_tile < absent
    rank = jnp.cumsum(live, axis=1, dtype=jnp.int32) - 1
    entry = (by_tile << slot_bits) + jnp.arange(slots, dtype=jnp.int32)[None, :]
    length = -(-slots // LANES) * LANES
    wanted = jnp.arange(length, dtype=jnp.int32)[None, :, None]
    lists = jnp.sum(
        jnp.where(live[:, None, :] & (rank[:, None, :] == wanted), entry[:, None, :], 0),
        axis=2,
    )
    return jnp.sum(live, axis=1, dtype=jnp.int32), lists, slot_bits


@functools.partial(
    jax.jit, static_argnames=("width", "out_dtype", "tile", "interpret")
)
def rows_by_place(rows, place, gate, *, width: int, out_dtype=jnp.float32,
                  tile: int = TOKEN_TILE, interpret: Optional[bool] = None):
    """``out[t] = Σ_j gate[t, j] · rows[place[t, j]]``, summed in float32:
    ``rows [R, 8m, 128]`` (:func:`to_tiles`), ``place [T, k]`` int32 (``R``
    or more: no row), ``gate [T, k]`` float32; ``out [T, width]`` of
    ``out_dtype``."""
    tokens, k = place.shape
    tile, padded = _tile(tokens, tile)
    absent = rows.shape[0]
    counts, lists, slot_bits = _fetch_lists(
        _pad_to(place.astype(jnp.int32).T, padded, 1, absent), absent, tile
    )
    length = lists.shape[1]
    out = pl.pallas_call(
        functools.partial(_by_place_kernel, slot_bits=slot_bits),
        grid=(padded // tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, length), lambda i: (0, i), memory_space=pltpu.SMEM),
            pl.BlockSpec((k, tile), lambda i: (0, i), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tile, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (padded, width), out_dtype, vma=_vma(rows, place, gate)
        ),
        scratch_shapes=[
            pltpu.VMEM((k * tile,) + rows.shape[1:], rows.dtype),
            pltpu.VMEM((tile,) + rows.shape[1:], jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=48 * 2**20
        ),
        interpret=_interpret(interpret),
        name="moe_rows_by_place",
    )(
        counts, lists.reshape(1, -1),
        _pad_to(gate.astype(jnp.float32).T, padded, 1, 0.0), rows,
    )
    return out[:tokens]
