"""End-to-end train-step tests on the 8-device CPU mesh.

The key distributed-correctness assertion (the reference never had one,
SURVEY.md §4): data-parallel training over 8 shards produces the SAME
parameter update as single-device training on the full batch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.data.pipeline import shard_batch
from distributeddeeplearning_tpu.data.synthetic import SyntheticImageDataset
from distributeddeeplearning_tpu.models.resnet import ResNet
from distributeddeeplearning_tpu.parallel.mesh import create_mesh
from distributeddeeplearning_tpu.training import (
    create_train_state,
    make_eval_step,
    make_train_step,
)
from distributeddeeplearning_tpu.training.train_step import replicate_state

CFG = TrainConfig(
    model="resnet18",
    num_classes=10,
    image_size=16,
    batch_size_per_device=2,
    weight_decay=1e-4,
    compute_dtype="float32",
)


def _model():
    return ResNet(depth=18, num_classes=10, dtype=jnp.float32)


def _batch(global_batch=16, seed=0):
    rng = np.random.RandomState(seed)
    images = rng.randn(global_batch, 16, 16, 3).astype(np.float32)
    labels = rng.randint(0, 10, size=(global_batch,)).astype(np.int32)
    return images, labels


@pytest.fixture(scope="module")
def setup(mesh8):
    model = _model()
    tx = optax.sgd(0.1, momentum=0.9)
    state = create_train_state(model, CFG, tx, input_shape=(1, 16, 16, 3))
    state = replicate_state(state, mesh8)
    step = make_train_step(model, tx, mesh8, CFG, donate_state=False)
    return model, tx, state, step


def test_train_step_runs_and_metrics(setup, mesh8):
    _, _, state, step = setup
    batch = shard_batch(_batch(), mesh8)
    new_state, metrics = step(state, batch)
    assert int(new_state.step) == 1
    assert np.isfinite(float(metrics["loss"]))
    assert 0.0 <= float(metrics["accuracy"]) <= 1.0
    assert float(metrics["grad_norm"]) > 0.0


def test_loss_decreases_on_fixed_batch(mesh8):
    # Plain SGD, no momentum/wd: with BN, conv kernels are scale-invariant
    # and momentum inflates their norm without changing CE, which would
    # make a loss that *includes* the L2 term non-monotone.
    model = _model()
    tx = optax.sgd(0.01)
    cfg = CFG.replace(weight_decay=0.0)
    state = replicate_state(
        create_train_state(model, cfg, tx, input_shape=(1, 16, 16, 3)), mesh8
    )
    step = make_train_step(model, tx, mesh8, cfg, donate_state=False)
    batch = shard_batch(_batch(), mesh8)
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


def test_dp_matches_single_device(mesh8):
    """8-way sharded update == single-device full-batch update.

    BN caveat: per-replica BN statistics (reference parity) make the
    *forward* differ between 1 and 8 shards, so for this equivalence test
    the batch is constructed so each shard has identical contents — then
    local BN stats equal global stats and updates must match exactly.
    """
    model = _model()
    tx = optax.sgd(0.1)
    state = create_train_state(model, CFG, tx, input_shape=(1, 16, 16, 3))

    shard_imgs, shard_labels = _batch(global_batch=2, seed=3)
    images = np.tile(shard_imgs, (8, 1, 1, 1))
    labels = np.tile(shard_labels, 8)

    # single-device reference update (no mesh)
    mesh1 = create_mesh(devices=jax.devices()[:1])
    step1 = make_train_step(model, tx, mesh1, CFG, donate_state=False)
    s1 = replicate_state(state, mesh1)
    s1, m1 = step1(s1, shard_batch((images, labels), mesh1))

    step8 = make_train_step(model, tx, mesh8, CFG, donate_state=False)
    s8 = replicate_state(state, mesh8)
    s8, m8 = step8(s8, shard_batch((images, labels), mesh8))

    # Compare the parameter *updates* by relative norm: f32 reduction-order
    # noise (16-sample reduce vs 8x2-shard + pmean, BN rsqrt) stays well
    # under 5%, while the bug class this guards (sum-instead-of-mean
    # gradient reduction) produces a ratio near 7.
    np.testing.assert_allclose(float(m1["loss"]), float(m8["loss"]), rtol=1e-4)
    for p0, a, b in zip(
        jax.tree.leaves(state.params),
        jax.tree.leaves(s1.params),
        jax.tree.leaves(s8.params),
    ):
        d1 = np.asarray(a) - np.asarray(p0)
        d8 = np.asarray(b) - np.asarray(p0)
        denom = np.linalg.norm(d1) + 1e-12
        assert np.linalg.norm(d8 - d1) / denom < 0.05


def test_eval_step(setup, mesh8):
    model, _, state, _ = setup
    eval_step = make_eval_step(model, mesh8)
    metrics = eval_step(state, shard_batch(_batch(), mesh8))
    for k in ("loss", "top1", "top5"):
        assert np.isfinite(float(metrics[k]))
    assert float(metrics["top5"]) >= float(metrics["top1"])
    assert float(metrics["count"]) == 16.0


def test_eval_step_masks_padded_samples(setup, mesh8):
    """Zero-weight slots must not affect metrics: same real samples with
    different garbage in the padded slots → identical metrics, count=10."""
    model, _, state, _ = setup
    eval_step = make_eval_step(model, mesh8)
    images, labels = _batch()
    weights = np.array([1.0] * 10 + [0.0] * 6, np.float32)

    def with_garbage(seed):
        rng = np.random.RandomState(seed)
        im = images.copy()
        lb = labels.copy()
        im[10:] = rng.randn(6, 16, 16, 3) * 50
        lb[10:] = rng.randint(0, 10, size=(6,))
        return im, lb, weights

    m1 = eval_step(state, shard_batch(with_garbage(1), mesh8))
    m2 = eval_step(state, shard_batch(with_garbage(2), mesh8))
    assert float(m1["count"]) == 10.0
    for k in ("loss", "top1", "top5"):
        np.testing.assert_allclose(float(m1[k]), float(m2[k]), rtol=1e-6)


def test_exact_evaluation_covers_every_sample_once(setup, mesh8):
    """Engine-level: synthetic exact val set of 100 @ global batch 16 →
    7 lockstep batches, exactly 100 weighted samples."""
    from distributeddeeplearning_tpu.training import loop

    model, _, state, _ = setup
    ds = SyntheticImageDataset(
        length=100,
        global_batch_size=16,
        image_size=16,
        num_classes=10,
        num_physical_batches=2,
        exact=True,
    )
    assert ds.steps_per_epoch == 7  # ceil(100/16)
    metrics = loop.evaluate(model, CFG, ds, state, mesh=mesh8)
    assert metrics["samples"] == 100.0
    for k in ("loss", "top1", "top5"):
        assert np.isfinite(metrics[k])


def test_synthetic_pipeline_through_train_step(setup, mesh8):
    _, _, state, step = setup
    ds = SyntheticImageDataset(
        length=64,
        global_batch_size=16,
        image_size=16,
        num_classes=10,
        num_physical_batches=2,
        seed=7,
    )
    n = 0
    for images, labels in ds.epoch(0):
        state, metrics = step(state, shard_batch((images, labels), mesh8))
        n += 1
    assert n == ds.steps_per_epoch == 4
    assert int(state.step) == 4


def test_weight_decay_changes_grads(mesh8):
    model = _model()
    tx = optax.sgd(0.1)
    cfg_nowd = CFG.replace(weight_decay=0.0)
    state = create_train_state(model, CFG, tx, input_shape=(1, 16, 16, 3))
    batch = shard_batch(_batch(), mesh8)

    s_wd = replicate_state(state, mesh8)
    s_nw = replicate_state(state, mesh8)
    _, m_wd = make_train_step(model, tx, mesh8, CFG, donate_state=False)(s_wd, batch)
    _, m_nw = make_train_step(model, tx, mesh8, cfg_nowd, donate_state=False)(
        s_nw, batch
    )
    assert float(m_wd["loss"]) > float(m_nw["loss"])  # L2 penalty added


def test_replica_axis_mesh_matches_plain_dp(mesh8):
    """Multi-slice shape: a (replica=2, data=4) mesh — replica is the
    DCN-outer axis — computes the identical update to the flat 8-way
    data mesh (the batch shards over replica×data and grads pmean over
    both axes)."""
    model = _model()
    tx = optax.sgd(0.1, momentum=0.9)
    images, labels = _batch()

    results = []
    for mesh in (
        create_mesh(axes=("replica", "data"), shape=(2, 4)),
        mesh8,
    ):
        state = replicate_state(create_train_state(model, CFG, tx), mesh)
        step = make_train_step(model, tx, mesh, CFG, donate_state=False)
        state, metrics = step(state, shard_batch((images, labels), mesh))
        results.append((float(metrics["loss"]), jax.device_get(state.params)))
    assert np.isclose(results[0][0], results[1][0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(results[0][1]), jax.tree.leaves(results[1][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def _ref_ce(logits, labels, ls=0.0):
    """Plain float32 CE for AD: take_along_axis, or one-hot targets."""
    c = logits.shape[-1]
    logp = jax.nn.log_softmax(logits)
    if ls > 0.0:
        on, off = 1.0 - ls, ls / (c - 1)
        targets = jax.nn.one_hot(labels, c) * (on - off) + off
        return -jnp.mean(jnp.sum(targets * logp, axis=-1))
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


VOCAB = 257  # no multiple of 128: the lanes' remainder is a case of its own


def _logits_with_a_tie(shape, dtype, seed=0):
    """Random logits whose first two rows hold their maximum twice, and
    labels that name the later of the two in row 0 (a miss, by
    ``jnp.argmax``'s rule: the first index wins) and the first in row 1."""
    rng = np.random.RandomState(seed)
    logits = (rng.randn(*shape, VOCAB) * 3).astype(np.float32)
    labels = rng.randint(0, VOCAB, shape).astype(np.int32)
    flat, flat_labels = logits.reshape(-1, VOCAB), labels.reshape(-1)
    for row, label in ((0, 200), (1, 17)):
        flat[row, [17, 200]] = 20.0
        flat_labels[row] = label
    flat_labels[2] = int(np.argmax(flat[2]))  # and a plain hit
    return jnp.asarray(logits).astype(dtype), jnp.asarray(labels)


@pytest.mark.parametrize("shape", [(8,), (2, 5)], ids=["BC", "BTC"])
@pytest.mark.parametrize("ls", [0.0, 0.1])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_sparse_ce_custom_vjp_matches_ad_reference(dtype, ls, shape):
    """The loss on the logits as they came (custom VJP, no float32 copy,
    no gather) against plain AD of the float32 reference on the upcast
    logits: value, gradient (in bfloat16: to one rounding, in the
    logits' dtype), and the hits against ``jnp.argmax`` with a tie."""
    from distributeddeeplearning_tpu.training.train_step import loss_and_hits

    logits, labels = _logits_with_a_tie(shape, dtype)
    (v_new, hits), g_new = jax.value_and_grad(
        lambda l: loss_and_hits(l, labels, ls), has_aux=True
    )(logits)
    v_ref, g_ref = jax.value_and_grad(lambda l: _ref_ce(l, labels, ls))(
        logits.astype(jnp.float32)
    )
    np.testing.assert_allclose(float(v_new), float(v_ref), rtol=1e-6)
    assert g_new.dtype == dtype
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(g_new), np.asarray(g_ref), atol=1e-5)
    else:  # round to nearest: half a unit of bfloat16's 8 bits
        np.testing.assert_allclose(
            np.asarray(g_new.astype(jnp.float32)), np.asarray(g_ref),
            rtol=2.0**-8, atol=1e-7,
        )
    want = jnp.argmax(logits, -1) == labels
    assert hits.shape == labels.shape and hits.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(hits), np.asarray(want, np.float32))
    assert [float(h) for h in hits.reshape(-1)[:3]] == [0.0, 1.0, 1.0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_weighted_loss_leaves_an_ignored_position_out(dtype):
    """Targets of −1: nothing forward (the loss is that of the kept
    positions over all N), nothing backward (a zero row), and whatever
    its logits say, no part in the accuracy's sum."""
    from distributeddeeplearning_tpu.training.train_step import (
        loss_and_hits,
        weighted_cross_entropy_loss,
    )

    logits, labels = _logits_with_a_tie((2, 6), dtype, seed=1)
    ignored = np.zeros((2, 6), bool)
    ignored[0, 0] = ignored[1, 3] = ignored[1, 5] = True
    targets = jnp.where(ignored, -1, labels)
    weights = jnp.asarray(np.random.RandomState(2).uniform(1, 8, (2, 6)), jnp.float32)
    (loss, hits), grad = jax.value_and_grad(
        lambda l: loss_and_hits(l, targets, 0.0, weights), has_aux=True
    )(logits)
    x = logits.astype(jnp.float32)
    per_token = -jnp.take_along_axis(jax.nn.log_softmax(x), labels[..., None], -1)[..., 0]
    want = jnp.sum(jnp.where(ignored, 0.0, per_token * weights)) / ignored.size
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    assert float(loss) == float(weighted_cross_entropy_loss(logits, targets, weights))
    assert not np.asarray(grad.astype(jnp.float32))[ignored].any()
    assert np.asarray(grad.astype(jnp.float32))[~ignored].any(axis=-1).all()
    # the ignored rows' logits are nobody's business
    moved = jnp.where(ignored[..., None], -logits, logits)
    loss2, hits2 = loss_and_hits(moved, targets, 0.0, weights)
    assert float(loss2) == float(loss)
    kept = ~ignored
    argmax_hits = np.asarray(jnp.argmax(logits, -1) == labels)
    np.testing.assert_array_equal(np.asarray(hits)[kept], argmax_hits[kept])
    np.testing.assert_array_equal(np.asarray(hits2)[kept], argmax_hits[kept])


@pytest.mark.parametrize("weighted", [False, True], ids=["mean", "weighted"])
def test_the_loss_keeps_no_float32_logits_and_gathers_nothing(weighted):
    """What the backward pass keeps of bfloat16 logits is the logits as
    they came, the labels and a float32 ``lse [N]``; and neither pass
    holds a gather or a scatter (the target's logit is a masked sum)."""
    from distributeddeeplearning_tpu.ops.losses import (
        _sparse_softmax_ce_fwd,
        loss_and_hits,
    )

    logits, labels = _logits_with_a_tie((4, 8), jnp.bfloat16)
    flat, flat_labels = logits.reshape(-1, VOCAB), labels.reshape(-1)
    _, residuals = _sparse_softmax_ce_fwd(flat, flat_labels, 0.0)
    kept = jax.tree.leaves(residuals)
    assert not [r for r in kept if r.shape == flat.shape and r.dtype != jnp.bfloat16]
    assert sorted((r.shape, str(r.dtype)) for r in kept) == sorted(
        [(flat.shape, "bfloat16"), ((32,), "int32"), ((32,), "float32")]
    )
    weights = jnp.ones(labels.shape, jnp.float32) if weighted else None
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda l: loss_and_hits(l, labels, 0.0, weights), has_aux=True
    ))(logits))
    assert "gather" not in text and "scatter" not in text and "argmax" not in text


@pytest.mark.parametrize("labels_kind", ["sparse", "onehot"])
def test_the_loss_counts_the_path_it_took_once_a_trace(labels_kind):
    """``loss.impl.<path>`` at trace time, as ``attn.impl.<path>`` is:
    one count a traced loss, forward and backward together."""
    from distributeddeeplearning_tpu import obs
    from distributeddeeplearning_tpu.training.train_step import cross_entropy_loss

    logits, labels = _logits_with_a_tie((2, 4), jnp.bfloat16)
    name = "loss.impl.xla"
    if labels_kind == "onehot":
        labels, name = jax.nn.one_hot(labels, VOCAB), "loss.impl.onehot"
    obs.reset()
    jax.jit(jax.value_and_grad(lambda l: cross_entropy_loss(l, labels))).lower(logits)
    totals = obs.get_bus().totals()
    assert totals[name] == {"kind": "counter", "count": 1, "sum": 1.0}
    assert [k for k in totals if k.startswith("loss.impl.")] == [name]
    (event,) = [e for e in obs.get_bus().ring if e["name"] == name]
    assert event["labels"] == {"dtype": "bfloat16", "shape": [2, 4, VOCAB]}
    obs.reset()


@pytest.mark.parametrize("name", ["lm_tiny", "gpt2_tiny"])
def test_the_steps_accuracy_is_the_argmaxs(name, mesh8):
    """``accuracy`` out of ``make_train_step`` comes from the loss's
    hits; it equals the share of positions whose argmax is the label, on
    the LM of ``models/transformer_lm.py`` and on the spec-built one."""
    from distributeddeeplearning_tpu.models import get_model

    seq, vocab = 16, 256
    cfg = TrainConfig(
        model=name, num_classes=vocab, compute_dtype="bfloat16",
        batch_size_per_device=1, weight_decay=0.0,
    )
    model = get_model(name, num_classes=vocab, dtype="bfloat16", max_seq_len=seq)
    tx = optax.sgd(0.1)
    state = create_train_state(
        model, cfg, tx, input_shape=(1, seq), input_dtype=jnp.int32
    )
    tokens = np.random.RandomState(3).randint(0, vocab, (8, seq)).astype(np.int32)
    logits = model.apply({"params": state.params}, jnp.asarray(tokens), train=True)
    first = np.asarray(jnp.argmax(logits, -1))
    # the model is right at the even positions and off by one elsewhere
    even = (np.arange(seq) % 2 == 0)[None, :]
    labels = np.where(even, first, (first + 1) % vocab).astype(np.int32)
    step = make_train_step(model, tx, mesh8, cfg, donate_state=False)
    _, metrics = step(replicate_state(state, mesh8), shard_batch((tokens, labels), mesh8))
    assert float(metrics["accuracy"]) == 0.5


def test_the_weight_draw_runs_no_forward_for_statistics_nobody_keeps():
    """A spec-built decoder's expert layers sow their pair counts, which
    ``model.init`` hands back beside the parameters; the state keeps
    ``params`` (and ``batch_stats``) alone, so the jitted draw returns
    those and XLA drops the forward: no product is left in the compiled
    program, and the parameters are flax's own draw, bit for bit."""
    import functools

    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.training.train_step import init_kept

    model = get_model("smallthinker_tiny", num_classes=64, dtype="float32", layers=4)
    x = jnp.zeros((1, 32), jnp.int32)
    rng = jax.random.PRNGKey(3)
    whole = jax.jit(lambda r, x: model.init(r, x, train=False)).lower(rng, x).compile()
    assert "stats" in whole(rng, x)
    assert " dot(" in whole.as_text()
    kept = jax.jit(functools.partial(init_kept, model)).lower(rng, x).compile()
    assert " dot(" not in kept.as_text()
    variables = kept(rng, x)
    assert set(variables) == {"params"}
    for a, b in zip(jax.tree.leaves(variables["params"]),
                    jax.tree.leaves(whole(rng, x)["params"])):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))
    cfg = TrainConfig(model="smallthinker_tiny", num_classes=64, optimizer="adamw")
    state = create_train_state(
        model, cfg, optax.sgd(0.1), rng=rng, input_shape=(1, 32), input_dtype=jnp.int32
    )
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(variables["params"])):
        assert bool(jnp.all(a == b))
