"""Train a decoder-only Transformer LM on synthetic tokens — explicit loop.

The long-context counterpart of the ImageNet examples: same engine, same
launcher, per-token cross-entropy, causal attention through the
configurable impl (on a TPU the default ``auto`` runs the flash kernel
from 640 tokens on; ``ATTN_IMPL=xla|pallas`` forces a path).

Run locally (CPU mesh smoke)::

    FAKE_DATA_LENGTH=2048 EPOCHS=1 BATCHSIZE=4 MODEL=lm_tiny \
        SEQ_LEN=128 VOCAB=1024 python examples/lm_synthetic_tpu.py

or across 2 processes::

    python launch.py -n 2 --devices-per-process 4 --platform cpu \
        --env FAKE_DATA_LENGTH=512 --env BATCHSIZE=2 --env SEQ_LEN=64 \
        --env VOCAB=256 examples/lm_synthetic_tpu.py

A decoder built from a layer spec (``models/decoder.py``) trains the same
way; ``OBJECTIVE=block_diffusion`` makes the staging noise each clean row
and the model read it as ``[noised | clean]`` (SDAR's block at a size for
a smoke run; ``sdar_30b_a3b`` is the published layer, of which a run
states its share through ``get_model``: ``layers``, ``experts_held``,
``first_expert``, as ``benchmarks/programs/sdar.py`` does)::

    python launch.py -n 2 --devices-per-process 4 --platform cpu \
        --env MODEL=sdar_tiny --env OBJECTIVE=block_diffusion \
        --env FAKE_DATA_LENGTH=512 --env BATCHSIZE=2 --env SEQ_LEN=64 \
        --env VOCAB=256 examples/lm_synthetic_tpu.py
"""

# Allow `python examples/<name>.py` from a repo checkout without an
# install: put the repo root (this file's parent's parent) on sys.path.
import os as _os
import sys as _sys

_sys.path.insert(
    0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
)


import os

import jax.numpy as jnp

from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.data.synthetic import SyntheticTokenDataset
from distributeddeeplearning_tpu.frontends import explicit
from distributeddeeplearning_tpu.models import get_model
from distributeddeeplearning_tpu.parallel import distributed
from distributeddeeplearning_tpu.utils.logging import get_logger, log_summary
from distributeddeeplearning_tpu.utils.timer import Timer


def main():
    distributed.maybe_initialize()
    import jax

    seq_len = int(os.environ.get("SEQ_LEN", "128"))
    vocab = int(os.environ.get("VOCAB", "32000"))
    # lm_tiny is only the default — MODEL=lm_base etc. must win (from_env
    # overrides beat the env, so don't pass model as an override).
    defaults = {} if "MODEL" in os.environ else {"model": "lm_tiny"}
    config = TrainConfig.from_env(num_classes=vocab, **defaults)
    logger = get_logger()
    logger.info("LM training: %s (seq_len=%d)", config.model, seq_len)

    model = get_model(
        config.model,
        **{**config.model_kwargs(), "num_classes": vocab},
        max_seq_len=seq_len,
    )
    data = SyntheticTokenDataset(
        length=config.fake_data_length,
        global_batch_size=config.global_batch_size,
        seq_len=seq_len,
        vocab_size=vocab,
        seed=config.seed,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
    )
    pieces, state = explicit.setup(
        model,
        config,
        steps_per_epoch=data.steps_per_epoch,
        input_shape=(1, seq_len),
        input_dtype=jnp.int32,
    )

    timer = Timer().start()
    for epoch in range(config.epochs):
        state = explicit.train_epoch(pieces, state, data, epoch)
    timer.stop()

    tokens = config.epochs * data.steps_per_epoch * config.global_batch_size
    log_summary(
        data_length=tokens,
        duration_s=timer.elapsed,
        batch_size_per_device=config.batch_size_per_device,
        num_devices=jax.device_count(),
        dataset_kind="synthetic-tokens",
    )


if __name__ == "__main__":
    main()
