"""ViT family: registry reachability + real train steps on the 8-device mesh.

BASELINE.json names ViT-B/16 as a required config; these tests drive the
tiny variant through the same compiled DP step the pod uses, with
dropout>0 so the rng threading (train_step rngs={'dropout': ...}) is
actually exercised.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.data.pipeline import shard_batch
from distributeddeeplearning_tpu.models import available_models, get_model
from distributeddeeplearning_tpu.models.vit import ViT
from distributeddeeplearning_tpu.training import (
    create_train_state,
    make_eval_step,
    make_train_step,
)
from distributeddeeplearning_tpu.training.train_step import replicate_state

CFG = TrainConfig(
    model="vit_ti16",
    num_classes=10,
    image_size=16,
    batch_size_per_device=2,
    weight_decay=0.0,
    compute_dtype="float32",
)


def _model(dropout=0.1):
    # 16x16 image / 16 patch -> 1 patch + cls token: smallest legal ViT.
    return ViT(
        variant="ti",
        patch_size=16,
        num_classes=10,
        dtype=jnp.float32,
        dropout=dropout,
    )


def _batch(global_batch=16, seed=0):
    rng = np.random.RandomState(seed)
    images = rng.randn(global_batch, 16, 16, 3).astype(np.float32)
    labels = rng.randint(0, 10, size=(global_batch,)).astype(np.int32)
    return images, labels


def test_registry_has_vit_family():
    names = available_models()
    for v in ("ti", "s", "b", "l", "h"):
        assert f"vit_{v}16" in names
    model = get_model("vit_b16", num_classes=10)
    assert isinstance(model, ViT)
    assert model.variant == "b" and model.patch_size == 16


def test_vit_b16_param_count():
    # Standard ViT-B/16 @224/1000 classes is ~86.6M params; count via
    # eval_shape so nothing is materialised.
    model = get_model("vit_b16")
    shapes = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((1, 224, 224, 3), jnp.float32), train=False),
        jax.random.PRNGKey(0),
    )
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes["params"]))
    assert 85e6 < n < 88e6, n


def test_vit_train_step_with_dropout(mesh8):
    """The regression VERDICT flagged: stochastic model through the DP step."""
    model = _model(dropout=0.1)
    tx = optax.sgd(0.05)
    state = replicate_state(
        create_train_state(model, CFG, tx, input_shape=(1, 16, 16, 3)), mesh8
    )
    step = make_train_step(model, tx, mesh8, CFG, donate_state=False)
    batch = shard_batch(_batch(), mesh8)
    state, metrics = step(state, batch)
    assert int(state.step) == 1
    assert np.isfinite(float(metrics["loss"]))


def test_vit_loss_decreases(mesh8):
    # Dropout on during training; measure progress with the deterministic
    # eval step so dropout noise can't flake the assertion.
    model = _model(dropout=0.1)
    tx = optax.sgd(0.05)
    state = replicate_state(
        create_train_state(model, CFG, tx, input_shape=(1, 16, 16, 3)), mesh8
    )
    step = make_train_step(model, tx, mesh8, CFG, donate_state=False)
    eval_step = make_eval_step(model, mesh8)
    batch = shard_batch(_batch(), mesh8)
    loss_before = float(eval_step(state, batch)["loss"])
    for _ in range(8):
        state, _ = step(state, batch)
    loss_after = float(eval_step(state, batch)["loss"])
    assert loss_after < loss_before, (loss_before, loss_after)


def test_vit_dropout_rng_varies_by_step(mesh8):
    """Same state+batch twice -> identical metrics (rng is a pure function
    of (seed, step, device)); consecutive steps -> different dropout masks,
    observable as different losses on the same fixed batch."""
    model = _model(dropout=0.5)
    tx = optax.sgd(0.0)  # lr 0: params never change, only step count
    state = replicate_state(
        create_train_state(model, CFG, tx, input_shape=(1, 16, 16, 3)), mesh8
    )
    step = make_train_step(model, tx, mesh8, CFG, donate_state=False)
    batch = shard_batch(_batch(), mesh8)
    s1, m1 = step(state, batch)
    _, m1b = step(state, batch)
    assert float(m1["loss"]) == float(m1b["loss"])  # deterministic replay
    _, m2 = step(s1, batch)
    assert float(m1["loss"]) != float(m2["loss"])  # new mask at new step


def test_vit_weight_decay_applies(mesh8):
    """Regression: logically-partitioned (boxed) params must still be seen
    by l2_kernel_penalty — params are unboxed in create_train_state."""
    model = _model(dropout=0.0)
    tx = optax.sgd(0.0)
    cfg_wd = CFG.replace(weight_decay=1e-2)
    state = create_train_state(model, CFG, tx, input_shape=(1, 16, 16, 3))
    batch = shard_batch(_batch(), mesh8)
    s_wd = replicate_state(state, mesh8)
    s_nw = replicate_state(state, mesh8)
    _, m_wd = make_train_step(model, tx, mesh8, cfg_wd, donate_state=False)(
        s_wd, batch
    )
    _, m_nw = make_train_step(model, tx, mesh8, CFG, donate_state=False)(s_nw, batch)
    assert float(m_wd["loss"]) > float(m_nw["loss"])


def test_vit_rejects_indivisible_image():
    with pytest.raises(ValueError):
        jax.eval_shape(
            lambda r: _model().init(
                r, jnp.zeros((1, 17, 17, 3), jnp.float32), train=False
            ),
            jax.random.PRNGKey(0),
        )


def test_vit_pallas_attention_matches_xla(mesh8):
    """The native tier reached from a real model: ViT with
    attn_impl='pallas' (flash kernel, interpreter mode on CPU) produces
    the same logits as the XLA einsum path and trains a step."""
    img = np.random.RandomState(0).randn(16, 32, 32, 3).astype(np.float32)
    lbl = np.random.RandomState(1).randint(0, 10, size=(16,)).astype(np.int32)

    def build(impl):
        m = ViT(
            variant="ti", patch_size=8, num_classes=10,
            dtype=jnp.float32, attn_impl=impl, dropout=0.0,
        )
        return m

    m_xla, m_fl = build("xla"), build("pallas")
    tx = optax.sgd(0.05)
    state = create_train_state(m_xla, CFG, tx, input_shape=(1, 32, 32, 3))
    logits_xla = m_xla.apply(
        {"params": state.params, "batch_stats": {}}, img, train=False
    )
    logits_fl = m_fl.apply(
        {"params": state.params, "batch_stats": {}}, img, train=False
    )
    np.testing.assert_allclose(
        np.asarray(logits_fl), np.asarray(logits_xla), atol=2e-4
    )
    # and the DP step runs through the kernel. check_vma=False only
    # because the Pallas HLO *interpreter* (CPU mesh) trips the checker;
    # the compiled TPU path runs with checking on (verified on a v5e).
    state = replicate_state(state, mesh8)
    step = make_train_step(m_fl, tx, mesh8, CFG, donate_state=False, check_vma=False)
    new_state, metrics = step(state, shard_batch((img, lbl), mesh8))
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_state.step) == 1


def test_get_model_attn_impl_plumbing():
    m = get_model("vit_ti16", num_classes=10, attn_impl="pallas")
    assert m.attn_impl == "pallas"
    # conv models ignore the knob instead of crashing
    r = get_model("resnet18", num_classes=10, attn_impl="pallas")
    assert r.depth == 18


def test_vit_fused_packed_attention_matches_xla(mesh8):
    """attn_impl='fused' (packed small-T kernel, interpreter mode on CPU)
    equals the XLA einsum path from the same params — the path the TPU
    'auto' default takes for ViT shapes (PROFILE.md round-4) — and trains
    a DP step. variant='s' because the packed kernel needs whole
    128-lane head groups (6 heads × d=64; 'ti' has 3 heads)."""
    img = np.random.RandomState(0).randn(16, 32, 32, 3).astype(np.float32)
    lbl = np.random.RandomState(1).randint(0, 10, size=(16,)).astype(np.int32)

    def build(impl):
        return ViT(
            variant="s", patch_size=8, num_classes=10,
            dtype=jnp.float32, attn_impl=impl, dropout=0.0,
        )

    m_xla, m_fused = build("xla"), build("fused")
    tx = optax.sgd(0.05)
    state = create_train_state(m_xla, CFG, tx, input_shape=(1, 32, 32, 3))
    logits_xla = m_xla.apply(
        {"params": state.params, "batch_stats": {}}, img, train=False
    )
    logits_fused = m_fused.apply(
        {"params": state.params, "batch_stats": {}}, img, train=False
    )
    np.testing.assert_allclose(
        np.asarray(logits_fused), np.asarray(logits_xla), atol=2e-4
    )
    state = replicate_state(state, mesh8)
    # default check_vma: ops/attention.kernel_interpreted covers impl='fused' off-TPU
    step = make_train_step(m_fused, tx, mesh8, CFG, donate_state=False)
    new_state, metrics = step(state, shard_batch((img, lbl), mesh8))
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_state.step) == 1


def test_vit_auto_impl_resolves_to_xla_off_tpu():
    """The 'auto' default must not select the Pallas kernel on non-TPU
    backends: logits equal the explicit-xla build bit-for-bit."""
    img = np.random.RandomState(0).randn(4, 32, 32, 3).astype(np.float32)
    m_auto = ViT(variant="s", patch_size=8, num_classes=10,
                 dtype=jnp.float32, attn_impl="auto", dropout=0.0)
    m_xla = ViT(variant="s", patch_size=8, num_classes=10,
                dtype=jnp.float32, attn_impl="xla", dropout=0.0)
    variables = m_xla.init(jax.random.PRNGKey(0), img[:1], train=False)
    a = m_auto.apply(variables, img, train=False)
    b = m_xla.apply(variables, img, train=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
