"""Pipeline-parallel tests (models/pipeline_lm.py + training/pp_step.py).

The oracle is ``PipelineLM.apply_reference`` — the same math run
sequentially on one device. The pipelined schedule (GPipe fill-drain,
ppermute hops, masked ramp ticks) must reproduce its loss and its
parameter update exactly; if a masked garbage tick leaked into the loss
or a psum double-counted a replicated grad, these comparisons break.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.models.pipeline_lm import PipelineLM
from distributeddeeplearning_tpu.parallel.mesh import create_mesh
from distributeddeeplearning_tpu.training.pp_step import (
    create_pp_state,
    make_pp_eval_step,
    make_pp_train_step,
    pp_state_specs,
)
from distributeddeeplearning_tpu.training.train_step import cross_entropy_loss

VOCAB, T = 32, 8
CFG = TrainConfig(num_classes=VOCAB, batch_size_per_device=1,
                  weight_decay=0.0, compute_dtype="float32")


def _pl(stages=4, layers=4, dropout=0.0):
    return PipelineLM(
        variant="tiny", vocab_size=VOCAB, max_seq_len=T,
        num_stages=stages, n_layers=layers, dtype=jnp.float32,
        dropout=dropout,
    )


def _rows(n, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, VOCAB, size=(n, T + 1)).astype(np.int32)


@pytest.fixture(scope="module")
def pp_mesh(devices):
    return create_mesh(axes=("data", "pipe"), shape=(2, 4))


def _put_batch(rows, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = NamedSharding(mesh, P("data"))
    return (
        jax.device_put(rows[:, :-1], spec),
        jax.device_put(rows[:, 1:], spec),
    )


def test_state_sharded_per_stage(pp_mesh):
    pl = _pl()
    tx = optax.sgd(0.1, momentum=0.9)
    state = create_pp_state(pl, CFG, tx, pp_mesh, T)
    leaf = jax.tree.leaves(state.params["stages"])[0]
    assert leaf.shape[0] == 4  # stacked stage axis
    assert tuple(leaf.sharding.spec)[:1] == ("pipe",)
    # optimizer momentum mirrors the stage sharding
    stage_moms = [
        l for l in jax.tree.leaves(state.opt_state)
        if getattr(l, "shape", ())[:1] == (4,)
    ]
    assert stage_moms
    for m in stage_moms:
        assert tuple(m.sharding.spec)[:1] == ("pipe",)
    emb = state.params["embed"]["tok_embed"]
    assert all(p is None for p in tuple(emb.sharding.spec))


def test_pp_matches_sequential_reference(pp_mesh):
    """One PP×DP step == the single-device update, exactly (f32)."""
    pl = _pl()
    tx = optax.sgd(0.1, momentum=0.9)
    rows = _rows(8)
    tokens, labels = rows[:, :-1], rows[:, 1:]

    state = create_pp_state(pl, CFG, tx, pp_mesh, T)
    host_params = jax.device_get(state.params)
    step = make_pp_train_step(pl, tx, pp_mesh, CFG, num_microbatches=2,
                              donate_state=False)
    new_state, metrics = step(state, _put_batch(rows, pp_mesh))

    def ref_loss(params):
        logits = pl.apply_reference(params, jnp.asarray(tokens), train=True)
        return cross_entropy_loss(logits, jnp.asarray(labels))

    loss_ref, grads_ref = jax.jit(jax.value_and_grad(ref_loss))(host_params)
    np.testing.assert_allclose(
        float(metrics["loss"]), float(loss_ref), rtol=1e-5
    )
    updates, _ = tx.update(grads_ref, tx.init(host_params), host_params)
    ref_new = jax.tree.map(lambda p, u: p + u, host_params, updates)
    got = jax.device_get(new_state.params)
    for (pa, a), (pb, b) in zip(
        sorted(jax.tree_util.tree_leaves_with_path(ref_new),
               key=lambda kv: str(kv[0])),
        sorted(jax.tree_util.tree_leaves_with_path(got),
               key=lambda kv: str(kv[0])),
    ):
        assert str(pa) == str(pb)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, err_msg=str(pa)
        )


def test_pp_loss_decreases(pp_mesh):
    pl = _pl()
    tx = optax.sgd(0.05)
    state = create_pp_state(pl, CFG, tx, pp_mesh, T)
    step = make_pp_train_step(pl, tx, pp_mesh, CFG, num_microbatches=4,
                              donate_state=False)
    batch = _put_batch(_rows(8), pp_mesh)
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses
    assert int(jax.device_get(state.step)) == 5


def test_pp_pipe_only_mesh(devices):
    """Pure pipeline (no data axis): 8 stages across all devices."""
    mesh = create_mesh(axes=("pipe",), shape=(8,))
    pl = _pl(stages=8, layers=8)
    tx = optax.sgd(0.1)
    state = create_pp_state(pl, CFG, tx, mesh, T)
    step = make_pp_train_step(pl, tx, mesh, CFG, num_microbatches=2,
                              donate_state=False)
    from jax.sharding import NamedSharding, PartitionSpec as P

    rows = _rows(4, seed=1)
    rep = NamedSharding(mesh, P())
    batch = (jax.device_put(rows[:, :-1], rep), jax.device_put(rows[:, 1:], rep))
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    state, m2 = step(state, batch)
    assert np.isfinite(float(m2["loss"]))
    assert int(jax.device_get(state.step)) == 2


def test_pp_eval_exact_coverage(pp_mesh):
    pl = _pl()
    tx = optax.sgd(0.1)
    state = create_pp_state(pl, CFG, tx, pp_mesh, T)
    eval_step = make_pp_eval_step(pl, pp_mesh)
    rows = _rows(8, seed=2)
    tokens, labels = rows[:, :-1], rows[:, 1:]
    m = eval_step(state, _put_batch(rows, pp_mesh))
    assert float(m["count"]) == 8 * T  # per-token counting
    assert np.isfinite(float(m["loss"]))
    # eval logits == sequential reference logits (loss comparison)
    ref_logits = pl.apply_reference(
        jax.device_get(state.params), jnp.asarray(tokens), train=False
    )
    from distributeddeeplearning_tpu.training.train_step import eval_metrics_fn

    sums = eval_metrics_fn(
        ref_logits, jnp.asarray(labels), jnp.ones((8,), jnp.float32)
    )
    np.testing.assert_allclose(
        float(m["loss"]), float(sums["loss"]) / float(sums["count"]), rtol=1e-5
    )


def test_pp_validation_errors(pp_mesh):
    pl = _pl(stages=3, layers=4)  # 4 % 3 != 0
    with pytest.raises(ValueError, match="not divisible"):
        pl.layers_per_stage
    pl4 = _pl()
    tx = optax.sgd(0.1)
    mesh_nopipe = create_mesh(devices=jax.devices())
    with pytest.raises(ValueError, match="pipe"):
        make_pp_train_step(pl4, tx, mesh_nopipe, CFG)


def test_pp_1f1b_matches_sequential_reference(pp_mesh):
    """The 1F1B schedule (hand-scheduled per-tick vjp, 2S-slot input ring
    buffer) computes the identical update to the sequential oracle — and
    therefore to the GPipe schedule."""
    pl = _pl()
    tx = optax.sgd(0.1, momentum=0.9)
    rows = _rows(8)
    tokens, labels = rows[:, :-1], rows[:, 1:]

    state = create_pp_state(pl, CFG, tx, pp_mesh, T)
    host_params = jax.device_get(state.params)
    step = make_pp_train_step(pl, tx, pp_mesh, CFG, num_microbatches=2,
                              schedule="1f1b", donate_state=False)
    new_state, metrics = step(state, _put_batch(rows, pp_mesh))

    def ref_loss(params):
        logits = pl.apply_reference(params, jnp.asarray(tokens), train=True)
        return cross_entropy_loss(logits, jnp.asarray(labels))

    loss_ref, grads_ref = jax.jit(jax.value_and_grad(ref_loss))(host_params)
    np.testing.assert_allclose(
        float(metrics["loss"]), float(loss_ref), rtol=1e-5
    )
    updates, _ = tx.update(grads_ref, tx.init(host_params), host_params)
    ref_new = jax.tree.map(lambda p, u: p + u, host_params, updates)
    got = jax.device_get(new_state.params)
    for (pa, a), (pb, b) in zip(
        sorted(jax.tree_util.tree_leaves_with_path(ref_new),
               key=lambda kv: str(kv[0])),
        sorted(jax.tree_util.tree_leaves_with_path(got),
               key=lambda kv: str(kv[0])),
    ):
        assert str(pa) == str(pb)
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, err_msg=str(pa)
        )


def test_pp_1f1b_with_weight_decay_and_more_microbatches(pp_mesh):
    """L2 closed-form grads + M > S scheduling (steady-state 1F1B)."""
    cfg = CFG.replace(weight_decay=5e-4)
    pl = _pl()
    tx = optax.sgd(0.1)
    rows = _rows(16, seed=3)
    tokens, labels = rows[:, :-1], rows[:, 1:]
    state = create_pp_state(pl, cfg, tx, pp_mesh, T)
    host_params = jax.device_get(state.params)
    step = make_pp_train_step(pl, tx, pp_mesh, cfg, num_microbatches=8,
                              schedule="1f1b", donate_state=False)
    new_state, metrics = step(state, _put_batch(rows, pp_mesh))

    from distributeddeeplearning_tpu.training.train_step import (
        l2_kernel_penalty,
    )

    def ref_loss(params):
        logits = pl.apply_reference(params, jnp.asarray(tokens), train=True)
        return cross_entropy_loss(logits, jnp.asarray(labels)) + (
            l2_kernel_penalty(params, cfg.weight_decay)
        )

    loss_ref, grads_ref = jax.jit(jax.value_and_grad(ref_loss))(host_params)
    np.testing.assert_allclose(
        float(metrics["loss"]), float(loss_ref), rtol=1e-5
    )
    updates, _ = tx.update(grads_ref, tx.init(host_params), host_params)
    ref_new = jax.tree.map(lambda p, u: p + u, host_params, updates)
    got = jax.device_get(new_state.params)
    for (pa, a), (pb, b) in zip(
        sorted(jax.tree_util.tree_leaves_with_path(ref_new),
               key=lambda kv: str(kv[0])),
        sorted(jax.tree_util.tree_leaves_with_path(got),
               key=lambda kv: str(kv[0])),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, err_msg=str(pa)
        )


def test_pp_schedules_agree_with_dropout(pp_mesh):
    """ADVICE r3: with dropout > 0 both schedules must draw the SAME
    noise — GPipe folds the per-device rng by microbatch index exactly
    like 1F1B — so the two schedules stay loss- and update-equivalent
    stochastically, not just in expectation."""
    pl = _pl(dropout=0.3)
    tx = optax.sgd(0.1)
    rows = _rows(8, seed=5)
    state = create_pp_state(pl, CFG, tx, pp_mesh, T)
    batch = _put_batch(rows, pp_mesh)
    outs = {}
    for schedule in ("gpipe", "1f1b"):
        step = make_pp_train_step(pl, tx, pp_mesh, CFG, num_microbatches=2,
                                  schedule=schedule, donate_state=False)
        new_state, metrics = step(state, batch)
        outs[schedule] = (float(metrics["loss"]),
                          jax.device_get(new_state.params))
    np.testing.assert_allclose(outs["gpipe"][0], outs["1f1b"][0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(outs["gpipe"][1]),
                    jax.tree.leaves(outs["1f1b"][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
