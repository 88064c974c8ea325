"""Explicit-loop front-end — the PyTorch-style path: you own the loop.

Parity with the reference's hand-written loop (``imagenet_pytorch_horovod
.py:204-239``: ``train()`` iterating the loader with zero_grad/forward/
backward/step, ``validate()``), minus everything TPU makes unnecessary:
no ``.cuda(non_blocking=True)`` (prefetch stages to HBM), no
``DistributedOptimizer`` (allreduce is inside the compiled step), no
``set_epoch`` on a sampler (datasets take the epoch index directly).

Usage::

    pieces = explicit.setup(model, config)
    for epoch in range(config.epochs):
        state = explicit.train_epoch(pieces, state, dataset, epoch)
        metrics = explicit.validate(pieces, state, val_dataset)
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterable, Optional, Tuple

import jax
import numpy as np
import optax

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.data.pipeline import prefetch_to_device
from distributeddeeplearning_tpu.data.noise import objective_transform
from distributeddeeplearning_tpu.training.metrics import (
    METRIC_KEYS,
    dispatch_step,
    log_sync,
)
from distributeddeeplearning_tpu.training.optimizer import create_optimizer
from distributeddeeplearning_tpu.training.state import TrainState
from distributeddeeplearning_tpu.utils.logging import get_logger
from distributeddeeplearning_tpu.utils.timer import Timer


@dataclasses.dataclass
class Pieces:
    """The compiled artifacts the explicit loop drives."""

    model: object
    config: TrainConfig
    mesh: object
    tx: optax.GradientTransformation
    train_step: Callable
    eval_step: Callable
    lr_schedule: optax.Schedule
    # Per-batch staging-sharding resolver (None → default over `data`).
    batch_sharding: Optional[Callable] = None
    # The objective's host transform of a batch, applied by the staging
    # (None → batches are placed as the dataset gives them).
    batch_transform: Optional[Callable] = None


def setup(
    model,
    config: TrainConfig,
    *,
    mesh=None,
    steps_per_epoch: Optional[int] = None,
    input_shape=None,
    input_dtype=None,
) -> Tuple[Pieces, TrainState]:
    """Build mesh, optimizer, compiled steps, and the initial state —
    the explicit analogue of reference ``main()`` setup (:267-338).

    ``input_shape``/``input_dtype`` override the image init contract for
    non-image models (LM: ``(1, seq_len)``, ``jnp.int32``).

    ``config.engine`` selects the runtime (dp / pjit / pp / sp) exactly
    as in ``loop.fit`` — both route through
    ``training.engines.build_engine``, the one dispatch point."""
    from distributeddeeplearning_tpu.training.engines import build_engine
    from distributeddeeplearning_tpu.training.loop import resolve_engine

    from distributeddeeplearning_tpu.parallel.mesh import dp_size

    with obs.span("setup.engine", engine=config.engine):
        _, mesh = resolve_engine(config, mesh)
        spe = steps_per_epoch or config.steps_per_epoch()
        tx, schedule = create_optimizer(config, spe, world_size=dp_size(mesh))
        # mesh placement, the step builders and the seeded weight draw
        eng = build_engine(
            model, config, tx, mesh,
            input_shape=input_shape, input_dtype=input_dtype,
        )
    pieces = Pieces(
        model=eng.model,
        config=config,
        mesh=mesh,
        tx=tx,
        train_step=eng.train_step,
        eval_step=eng.eval_step,
        lr_schedule=schedule,
        batch_sharding=eng.batch_sharding,
        batch_transform=objective_transform(config),
    )
    return pieces, eng.state


def train_epoch(
    pieces: Pieces,
    state: TrainState,
    data,
    epoch: int,
    log_every: Optional[int] = None,
) -> TrainState:
    """One epoch (reference ``train()`` :204-221, incl. its per-100-steps
    duration/loss logging). Emits what ``loop.fit`` emits a step: the
    ``step`` span round the dispatch, ``step.log_sync`` round the loss
    read-back, and the staging spans of ``prefetch_to_device``."""
    log = get_logger()
    cfg = pieces.config
    log_every = log_every if log_every is not None else cfg.log_every_steps
    timer = Timer().start()
    for i, batch in enumerate(
        prefetch_to_device(
            data.epoch(epoch), pieces.mesh, size=cfg.prefetch_batches,
            sharding=pieces.batch_sharding, transform=pieces.batch_transform,
        )
    ):
        state, metrics = dispatch_step(
            pieces.train_step, state, batch, epoch=epoch
        )
        if log_every and (i + 1) % log_every == 0:
            with log_sync(epoch=epoch):
                # one read-back: the loss and whatever the step reports
                # beside the metric contract (an expert layer's counts)
                read = jax.device_get(
                    {k: v for k, v in metrics.items()
                     if k == "loss" or k not in METRIC_KEYS}
                )
            loss = float(read.pop("loss"))
            for name, value in read.items():
                obs.counter(name, float(value), epoch=epoch)
            log.info(
                "step %d loss=%.4f elapsed=%.2fs", i + 1, loss, timer.elapsed,
                extra={"epoch": epoch},
            )
    return state


def validate(pieces: Pieces, state: TrainState, data) -> Dict[str, float]:
    """Full-dataset eval (reference ``validate()`` :224-239)."""
    from distributeddeeplearning_tpu.training.loop import _run_eval

    return _run_eval(
        pieces.eval_step, state, data, pieces.mesh, pieces.config,
        sharding=pieces.batch_sharding,
    )
