"""The program's side of the ``granite`` family: the only module of the
family that imports the system under test. It builds the program's own
model and trainer from a configuration file (the next-token objective,
the model's own remat where the traffic asks for it) and hands them the
benchmark's weights. ``programs/smallthinker.py`` less the routing: the
model has no experts, and the run's share of it is its depth alone. The
traffic file may state how often the loop reads its loss back
(``log_every_steps``, the trainer's own setting): a read-back waits for
the device, and at the default of 100 steps of 0.4 s the wait of the
window's only read-back covers the seconds in which the runner would
start its trace."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.programs.gpt2 import (  # noqa: F401  (the runner's)
    check_layout,
    first_moment,
    stage_like,
    train_epoch,
)


def build_trainer(cfg: dict, job: dict, chips: int, seed: int, params: dict):
    """``explicit.setup`` on a ``chips``-wide data-parallel mesh, the
    benchmark's weights put in place of the program's own draw. Returns
    ``(pieces, state)``."""
    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.frontends import explicit
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.parallel.mesh import data_parallel_mesh

    opt = job["optimizer"]
    config = TrainConfig(
        model=cfg["program"]["model"], num_classes=cfg["vocab_size"],
        compute_dtype=cfg["compute_dtype"],
        batch_size_per_device=int(job["batch_per_chip"]),
        remat=bool(job.get("remat", False)),
        optimizer=opt["name"], base_lr=opt["learning_rate"],
        adam_beta1=opt["adam_beta1"], adam_beta2=opt["adam_beta2"],
        adam_eps=opt["adam_eps"],
        decoupled_weight_decay=opt["decoupled_weight_decay"],
        weight_decay=0.0, label_smoothing=0.0, warmup_epochs=0,
        lr_schedule="constant", scale_lr_by_world_size=False,
        fake=True, epochs=1, seed=int(seed) & 0x7FFFFFFF,
        **({"log_every_steps": int(job["log_every_steps"])}
           if "log_every_steps" in job else {}),
    )
    model = get_model(
        config.model, **config.model_kwargs(), layers=cfg["layers"],
        max_seq_len=cfg["max_position_embeddings"],
    )
    pieces, state = explicit.setup(
        model, config, mesh=data_parallel_mesh(chips), steps_per_epoch=1000,
        input_shape=(1, int(job["seq_len"])), input_dtype=jnp.int32,
    )
    check_layout(params, state.params)
    # no copy: at 2.9 GiB a copy of the parameters the benchmark's own
    # tree goes in as it is (the runner lets go of it at once)
    placed = jax.tree.map(
        lambda new, old: jax.device_put(new, old.sharding), params, state.params
    )
    return pieces, state.replace(params=placed)
