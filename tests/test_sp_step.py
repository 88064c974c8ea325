"""Sequence-parallel LM training: DP×SP step matches single-device.

The core long-context claim: sharding the sequence over a mesh axis
(ring attention + globalised positions) produces the SAME training
update as unsharded training — asserted against a plain single-device
step on the full batch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.data.pipeline import shard_batch
from distributeddeeplearning_tpu.models.transformer_lm import TransformerLM
from distributeddeeplearning_tpu.parallel.mesh import create_mesh
from distributeddeeplearning_tpu.training import (
    create_train_state,
    make_sp_train_step,
)
from distributeddeeplearning_tpu.training.train_step import (
    cross_entropy_loss,
    replicate_state,
)
from jax.sharding import NamedSharding, PartitionSpec as P

VOCAB = 32
T = 32  # global sequence; 8 tokens per seq shard on the 2x4 mesh
B = 4
CFG = TrainConfig(
    num_classes=VOCAB, batch_size_per_device=2, weight_decay=0.0,
    compute_dtype="float32",
)


def _model(seq_axis=None, impl="xla"):
    return TransformerLM(
        variant="tiny", vocab_size=VOCAB, max_seq_len=T,
        dtype=jnp.float32, attn_impl=impl, seq_axis=seq_axis,
    )


def _batch(seed=0):
    rng = np.random.RandomState(seed)
    rows = rng.randint(0, VOCAB, size=(B, T + 1)).astype(np.int32)
    return rows[:, :-1], rows[:, 1:]


@pytest.fixture(scope="module")
def sp_mesh(devices):
    return create_mesh(axes=("data", "seq"), shape=(2, 4))


def test_sp_step_matches_single_device(sp_mesh):
    """One DP×SP step == one full-batch single-device step (params+loss)."""
    tx = optax.sgd(0.1)
    sp_model = _model(seq_axis="seq", impl="ring")
    ref_model = _model()
    state0 = create_train_state(
        ref_model, CFG, tx, input_shape=(1, T), input_dtype=jnp.int32
    )
    tokens, labels = _batch()

    # reference: plain single-device step on the full [B, T] batch
    def ref_step(params, opt_state):
        def loss_fn(p):
            logits = ref_model.apply({"params": p}, tokens, train=False)
            return cross_entropy_loss(logits, labels)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return jax.tree.map(lambda p, u: p + u, params, updates), loss

    ref_params, ref_loss = ref_step(state0.params, state0.opt_state)

    # SP: tokens sharded over (data, seq)
    spec = NamedSharding(sp_mesh, P("data", "seq"))
    sp_state = replicate_state(state0, sp_mesh)
    step = make_sp_train_step(sp_model, tx, sp_mesh, CFG, donate_state=False)
    batch = (
        jax.device_put(tokens, spec),
        jax.device_put(labels, spec),
    )
    new_state, metrics = step(sp_state, batch)

    np.testing.assert_allclose(float(metrics["loss"]), float(ref_loss), rtol=1e-5)
    for a, b in zip(
        jax.tree.leaves(new_state.params), jax.tree.leaves(ref_params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_sp_step_loss_decreases(sp_mesh):
    tx = optax.sgd(0.5)
    model = _model(seq_axis="seq", impl="ring")
    state = replicate_state(
        create_train_state(
            model, CFG, tx, input_shape=(1, T), input_dtype=jnp.int32
        ),
        sp_mesh,
    )
    step = make_sp_train_step(model, tx, sp_mesh, CFG, donate_state=False)
    spec = NamedSharding(sp_mesh, P("data", "seq"))
    tokens, labels = _batch(seed=3)
    batch = (jax.device_put(tokens, spec), jax.device_put(labels, spec))
    losses = []
    for _ in range(4):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


def test_sp_step_rejects_mismatched_model(sp_mesh):
    tx = optax.sgd(0.1)
    with pytest.raises(ValueError, match="seq_axis"):
        make_sp_train_step(_model(), tx, sp_mesh, CFG)


def test_sp_step_rejects_non_ring_impl(sp_mesh):
    tx = optax.sgd(0.1)
    with pytest.raises(ValueError, match="ring"):
        make_sp_train_step(_model(seq_axis="seq", impl="xla"), tx, sp_mesh, CFG)


def test_sp_step_rejects_overlong_global_sequence(sp_mesh):
    """max_seq_len guards the GLOBAL sequence: local shards would pass the
    model's own check while dynamic_slice silently clamps positions."""
    tx = optax.sgd(0.1)
    model = TransformerLM(
        variant="tiny", vocab_size=VOCAB, max_seq_len=T // 2,  # global T too long
        dtype=jnp.float32, attn_impl="ring", seq_axis="seq",
    )
    state = replicate_state(
        create_train_state(
            model, CFG, tx, input_shape=(1, T // 2), input_dtype=jnp.int32
        ),
        sp_mesh,
    )
    step = make_sp_train_step(model, tx, sp_mesh, CFG, donate_state=False)
    spec = NamedSharding(sp_mesh, P("data", "seq"))
    tokens, labels = _batch()
    with pytest.raises(ValueError, match="exceeds model.max_seq_len"):
        step(
            state,
            (jax.device_put(tokens, spec), jax.device_put(labels, spec)),
        )


def test_ring_rejects_unsharded_sequence(sp_mesh):
    """A bound-but-unsharded ring axis must raise, not compute garbage."""
    from distributeddeeplearning_tpu.parallel.ring_attention import ring_attention

    def f(q):
        return ring_attention(q, q, q, axis_name="seq")

    q = jnp.zeros((2, 8, 2, 8), jnp.float32)
    with pytest.raises(ValueError, match="must actually be sharded"):
        jax.jit(
            jax.shard_map(
                f, mesh=sp_mesh, in_specs=P(), out_specs=P()
            )
        )(q)
