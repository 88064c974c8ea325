"""The program's side of the ``gpt2`` family: the only module of the
family that imports the system under test. It builds the program's own
model, server and trainer from a configuration file and hands them the
benchmark's weights."""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp


def _model(cfg: dict, **kw):
    from distributeddeeplearning_tpu.models import get_model

    return get_model(
        cfg["program"]["model"], num_classes=cfg["vocab_size"],
        max_seq_len=cfg["n_positions"], dtype=cfg["compute_dtype"], **kw,
    )


def check_layout(params: dict, like: dict) -> None:
    """The benchmark's tree must be the program's, leaf for leaf."""
    a = {jax.tree_util.keystr(k): v.shape
         for k, v in jax.tree_util.tree_leaves_with_path(params)}
    b = {jax.tree_util.keystr(k): v.shape
         for k, v in jax.tree_util.tree_leaves_with_path(like)}
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))[:6]
        raise ValueError(f"configuration and program disagree on shapes: {diff}")


def build_server(cfg: dict, server_kw: dict, params: dict,
                 buckets: Optional[Sequence[int]]):
    """``Server.build`` over a warmed ``SlotEngine``: the server's normal
    path. ``server_kw`` are ``ServeConfig`` fields from the traffic file."""
    from distributeddeeplearning_tpu.serving import ServeConfig, Server

    model = _model(cfg)
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, model.max_seq_len), jnp.int32), train=False,
        )["params"]
    )
    import flax.linen as nn

    check_layout(params, nn.unbox(shapes))
    config = ServeConfig(
        **server_kw, buckets=tuple(buckets) if buckets else None,
    )
    server = Server.build(model, params, config)
    server.engine.warmup()
    return server


def serve_request(prompt, max_new_tokens: int, on_token):
    from distributeddeeplearning_tpu.serving import Request

    return Request(
        prompt=prompt, max_new_tokens=max_new_tokens, temperature=0.0,
        eos_token=None, on_token=on_token,
    )


def build_trainer(cfg: dict, job: dict, chips: int, seed: int, params: dict):
    """``explicit.setup`` on a ``chips``-wide data-parallel mesh, with
    the benchmark's weights put in place of the program's own draw (the
    optimizer's state starts at zero either way). Returns
    ``(pieces, state)``."""
    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.frontends import explicit
    from distributeddeeplearning_tpu.parallel.mesh import data_parallel_mesh

    opt = job["optimizer"]
    config = TrainConfig(
        model=cfg["program"]["model"], num_classes=cfg["vocab_size"],
        compute_dtype=cfg["compute_dtype"],
        batch_size_per_device=int(job["batch_per_chip"]),
        optimizer=opt["name"], base_lr=opt["learning_rate"],
        adam_beta1=opt["adam_beta1"], adam_beta2=opt["adam_beta2"],
        adam_eps=opt["adam_eps"],
        decoupled_weight_decay=opt["decoupled_weight_decay"],
        weight_decay=0.0, label_smoothing=0.0, warmup_epochs=0,
        lr_schedule="constant", scale_lr_by_world_size=False,
        fake=True, epochs=1, seed=int(seed) & 0x7FFFFFFF,
    )
    model = _model(cfg, attn_impl=config.attn_impl)
    pieces, state = explicit.setup(
        model, config, mesh=data_parallel_mesh(chips), steps_per_epoch=1000,
        input_shape=(1, int(job["seq_len"])), input_dtype=jnp.int32,
    )
    check_layout(params, state.params)
    placed = jax.tree.map(
        lambda new, old: jax.device_put(jnp.copy(new), old.sharding),
        params, state.params,
    )
    return pieces, state.replace(params=placed)


def stage_like(pieces, batch):
    """A host batch placed as ``train_epoch`` places it."""
    from distributeddeeplearning_tpu.data.pipeline import shard_batch

    sharding = pieces.batch_sharding
    if callable(sharding):
        sharding = sharding(batch)
    return shard_batch(batch, pieces.mesh, sharding)


def train_epoch(pieces, state, data, epoch: int, log_every=None):
    from distributeddeeplearning_tpu.frontends import explicit

    return explicit.train_epoch(pieces, state, data, epoch, log_every=log_every)


def first_moment(opt_state):
    """Adam's first moment out of the optimizer's state, whatever wraps
    it: the one node that has a ``mu``."""
    found = [
        node for node in jax.tree.leaves(
            opt_state, is_leaf=lambda n: hasattr(n, "mu")
        ) if hasattr(node, "mu")
    ]
    if len(found) != 1:
        raise ValueError(f"expected one Adam state, found {len(found)}")
    return found[0].mu
