"""Block-diffusion noising of clean token rows, on the host.

The masked objective of BD3-LM (arXiv:2503.09573), which SDAR
(arXiv:2510.06303) adapts an autoregressive model with: a row ``x0`` of
``L`` tokens in blocks of ``B``; each block draws a level ``t ~
U(t_min, 1)`` and each of its tokens is replaced by the mask id with
probability ``t``, giving ``x_t``. The model reads ``[x_t ‖ x0]``
(``models/decoder.py``), a masked position's target is its own clean
token, and the loss weighs it by ``1/t``.

The draw is a **pure function of the clean row** and of the job's
constants, so that whoever holds the batches can redo it without this
module (the benchmark's plain reference does): a row's generator is
``numpy.random.PCG64`` seeded with the CRC-32 of its little-endian int32
bytes; it gives the ``L/B`` levels first (``t_min + (1 − t_min)·u``),
then one uniform a token, masked where that lies under its block's
level.
"""

from __future__ import annotations

import zlib
from typing import Callable, Optional, Tuple

import numpy as np

from distributeddeeplearning_tpu import obs

IGNORE = -1  # target of a position the loss leaves out


def block_diffusion_noise(
    tokens: np.ndarray, *, block_len: int, t_min: float, mask_id: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Clean rows ``[R, L]`` -> ``(inputs [R, 2L] int32: noised then
    clean, targets [R, L] int32: the clean token where masked and −1
    elsewhere, weights [R, L] float32: 1/t where masked and 0
    elsewhere)``."""
    tokens = np.asarray(tokens, np.int32)
    rows, length = tokens.shape
    if length % block_len:
        raise ValueError(f"rows of {length} tokens are no whole {block_len}-blocks")
    masked = np.empty((rows, length), bool)
    level = np.empty((rows, length), np.float64)
    for r, row in enumerate(tokens):
        rng = np.random.Generator(
            np.random.PCG64(zlib.crc32(row.astype("<i4").tobytes()))
        )
        t = t_min + (1.0 - t_min) * rng.random(length // block_len)
        level[r] = np.repeat(t, block_len)
        masked[r] = rng.random(length) < level[r]
    noised = np.where(masked, np.int32(mask_id), tokens)
    return (
        np.concatenate([noised, tokens], axis=1),
        np.where(masked, tokens, np.int32(IGNORE)),
        np.where(masked, 1.0 / level, 0.0).astype(np.float32),
    )


def objective_transform(config) -> Optional[Callable]:
    """The host transform that ``config.objective`` asks of the staging:
    None for next-token training; for block diffusion, ``(tokens, _) ->
    (inputs, targets, weights)`` under span ``data.noise`` (the batch's
    second element, a next-token dataset's labels, is not used)."""
    if config.objective == "next_token":
        return None
    if config.objective != "block_diffusion":
        raise ValueError(f"unknown objective {config.objective!r}")
    mask_id = config.mask_token_id
    if mask_id is None:
        mask_id = config.num_classes - 1

    def noise(batch):
        with obs.span("data.noise"):
            return block_diffusion_noise(
                np.asarray(batch[0]), block_len=config.diffusion_block,
                t_min=config.diffusion_t_min, mask_id=mask_id,
            )

    return noise
