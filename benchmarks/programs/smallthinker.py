"""The program's side of the ``smallthinker`` family: the only module of
the family that imports the system under test. It builds the program's
own model and trainer from a configuration file (the next-token
objective, the model's own remat where the traffic asks for it) and hands
them the benchmark's weights."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.programs.gpt2 import (  # noqa: F401  (the runner's)
    check_layout,
    first_moment,
    stage_like,
    train_epoch,
)


def _share(cfg: dict) -> dict:
    """The run's share of the published model, as ``get_model`` takes it."""
    return {
        "layers": cfg["layers"],
        "experts_held": cfg["moe_num_primary_experts"],
        "first_expert": cfg.get("first_expert", 0),
        "max_seq_len": cfg["max_position_embeddings"],
    }


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
    )
    model = get_model(config.model, **config.model_kwargs(), **_share(cfg))
    pieces, state = explicit.setup(
        model, config, mesh=data_parallel_mesh(chips), steps_per_epoch=1000,
        input_shape=(1, int(job["seq_len"])), input_dtype=jnp.int32,
    )
    check_layout(params, state.params)
    # no copy: at 2.4 GiB a copy of the parameters the benchmark's own
    # tree goes in as it is (the runner lets go of it at once)
    placed = jax.tree.map(
        lambda new, old: jax.device_put(new, old.sharding), params, state.params
    )
    return pieces, state.replace(params=placed)


def routing_choices(cfg: dict, params: dict, inputs: np.ndarray):
    """The experts the program's model chooses for ``inputs [R, T]``
    under ``params``, ``[layers, R·T, top_k]``: one forward pass of the
    model as the trainer builds it."""
    from distributeddeeplearning_tpu.models import get_model

    model = get_model(
        cfg["program"]["model"], num_classes=cfg["vocab_size"],
        dtype=cfg["compute_dtype"], **_share(cfg),
    )

    @jax.jit
    def chosen(params, inputs):
        _, seen = model.apply(
            {"params": params}, inputs, train=False, mutable=["intermediates"]
        )
        return jnp.stack([
            seen["intermediates"][f"block{i}"]["mlp"]["experts"][0]
            for i in range(cfg["layers"])
        ])

    return np.asarray(chosen(params, jnp.asarray(inputs)))
